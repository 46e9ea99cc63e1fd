"""The LSM engine: memtable + L0 runs + leveled SSTs, device-offloaded
flush/compaction.

Replaces the reference's RocksDB-behind-rocksdb_wrapper
(src/server/rocksdb_wrapper.{h,cpp}) with a from-scratch LSM designed around
KVBlocks: writes land in a dict memtable, flush sorts the block on the
configured backend, compaction feeds sorted runs to ops.compact_blocks.
There is deliberately NO internal WAL: exactly like the reference (which
disables RocksDB's WAL), the replication mutation log is the WAL and replays
into the engine on recovery (SURVEY.md §3.2 note; replication.mutation_log).

Structure:
  - L0: overlapping whole-keyspace runs, newest first (flush outputs).
  - L1..max_levels: runs of non-overlapping range-partitioned files sorted
    by min_key; compaction output is split at target_file_size_bytes so a
    later ranged compaction touches a bounded byte budget, not the whole DB.
  - L0 threshold merges L0 + overlapping L1 files into L1; size-ratio
    overflow cascades one file (+ overlap) per step into the next level.

Durability/decree bookkeeping mirrors the reference invariants (SURVEY.md §7b):
  - every committed batch records its decree in the in-memory meta store
    (reference: LAST_FLUSHED_DECREE put into the meta CF within each
    WriteBatch, src/server/rocksdb_wrapper.cpp:143);
  - the manifest's last_flushed_decree only advances to decrees whose data
    is FULLY covered by on-disk SSTs: each memtable records the last decree
    it contains at rotation, and flushing (oldest-first) advances durability
    to that memtable's decree — never to decrees still sitting in younger
    memtables (the reference reads the meta CF with kPersistedTier for the
    same reason, src/server/meta_store.cpp:129).
"""

import bisect
import heapq
import json
import os
import shutil
import struct
import threading
import time
from dataclasses import dataclass, field

import numpy as np

_UNRESOLVED = object()  # LsmEngine._resolved_mesh: "not probed yet"

from ..base.crc64 import crc64_batch
from ..base.key_schema import key_hash
from ..base.utils import epoch_now
from ..base.value_schema import check_if_ts_expired
from ..runtime.fail_points import fail_point
from ..runtime import events, lockrank
from ..ops.compact import CompactOptions, compact_blocks, sort_block
from ..ops.packing import DEFAULT_PREFIX_U32
from .block import KVBlock
from .memtable import Memtable
from .sstable import CorruptionError, SSTable, verify_sst, write_sst

MANIFEST = "MANIFEST"
CHECKPOINT_PREFIX = "checkpoint."

# range-read totals resolved once (PR 6's rule: the registry lock is
# per-lookup and these fire on every multi_get range / sortkey_count /
# scanner batch)
from ..runtime.perf_counters import counters as _counters  # noqa: E402

_C_RANGE_BATCH = _counters.number("read.range.batch_count")
_C_RANGE_ROWS = _counters.number("read.range.rows")
_C_RANGE_DEVICE = _counters.number("read.range.device_count")
_C_RANGE_HOST = _counters.number("read.range.host_count")
# (range, SST) bounds walked by SSTable.lower_bound, in whichever batch:
# device_count above counts every range of a batch that was ELIGIBLE for
# the device, and an SST with fewer candidates than device_read_min_batch
# resolves here all the same. The device's own share is
# read.range.device_ranges (ops/device_lookup.py)
_C_RANGE_HOST_RANGES = _counters.number("read.range.host_ranges")
_C_RANGE_REV_HOST = _counters.number("read.range.reverse_host_count")
# which way _scan_over served a range that had rows to read: as slices of
# its one SST's block, or through the k-way heap merge
_C_RANGE_SLICE = _counters.number("read.range.slice_ranges")
_C_RANGE_MERGED = _counters.number("read.range.merged_ranges")
# monotonic totals of the two quiet device bypasses this module owns: a
# failed residency prime (file stays host-packed) and a mesh that would
# not resolve (manual_compact stays single-chip)
_C_PRIME_FAIL = _counters.number("engine.hbm.prime_fail_count")
_C_MESH_FAIL = _counters.number("engine.compact.mesh_fail_count")


def _count_rows(it):
    """Wrap a merged-scan iterator with read.range.rows accounting — one
    bulk counter add per iterator lifetime (close/exhaustion), not one
    registry hit per row."""
    c = 0
    try:
        for rec in it:
            c += 1
            yield rec
    finally:
        if c:
            _C_RANGE_ROWS.increment(c)


# rows a single-source range cuts from its SST's block at once. Kept under
# 500: numpy gives the GIL up inside a loop over more than 500 elements,
# and a serving thread that gives it up waits out the other threads'
# switch intervals to get it back
_SLICE_ROWS = 256


def _span(arena, off, ln):
    """-> (starts, ends, buf): rows' bounds within buf, which is ONE
    tobytes() of the arena span the rows lie in."""
    end = off + ln
    base = int(off.min())
    return ((off - base).tolist(), (end - base).tolist(),
            arena[base:int(end.max())].tobytes())


def _slice_rows(b, lo, hi, now, include_deleted, reverse, flagged=False):
    """Rows [lo, hi) of one sorted block whose keys are unique (descending
    when `reverse`), _SLICE_ROWS at a time, each row's key and value cut
    from its chunk's two arena spans. Unless `include_deleted`, one mask a
    chunk drops tombstones and expired rows (check_if_ts_expired's
    0 < expire_ts <= now). -> the (key, value, expire_ts) tuples the merge
    yields; `flagged` adds each row's tombstone flag (a heap source's
    rows, which the merge itself filters)."""
    chunks = range(lo, hi, _SLICE_ROWS)
    for i in (reversed(chunks) if reverse else chunks):
        j = min(i + _SLICE_ROWS, hi)
        cols = [b.key_off[i:j], b.key_len[i:j], b.val_off[i:j],
                b.val_len[i:j], b.expire_ts[i:j], b.deleted[i:j]]
        if not include_deleted:
            e = cols[4]
            live = ~cols[5] & ((e == 0) | (e > now))
            if not live.all():
                if not live.any():
                    continue
                cols = [c[live] for c in cols]
        if reverse:
            cols = [c[::-1] for c in cols]
        ko, kl, vo, vl, e, d = cols
        ks, ke, kb = _span(b.key_arena, ko, kl)
        vs, ve, vb = _span(b.val_arena, vo, vl)
        if flagged:
            yield from [(kb[p:q], vb[r:s], x, y) for p, q, r, s, x, y
                        in zip(ks, ke, vs, ve, e.tolist(), d.tolist())]
        else:
            yield from [(kb[p:q], vb[r:s], x) for p, q, r, s, x
                        in zip(ks, ke, vs, ve, e.tolist())]

# meta-store keys (reference: src/server/meta_store.cpp:29)
META_DATA_VERSION = "pegasus_data_version"
META_LAST_FLUSHED_DECREE = "pegasus_last_flushed_decree"
META_LAST_MANUAL_COMPACT_FINISH_TIME = "pegasus_last_manual_compact_finish_time"


@dataclass
class EngineOptions:
    memtable_bytes: int = 64 << 20
    l0_compaction_trigger: int = 4
    backend: str = "cpu"            # compaction_backend: "cpu" | "tpu"
    # the CAP on a run's key window, in u32 lanes (ops/packing.py): a run
    # packs what its longest key needs, so 26 B keys take 7 lanes, the geo
    # index table's 51 B keys 13; only keys over 64 B need suffix ranks
    prefix_u32: int = DEFAULT_PREFIX_U32
    data_version: int = 2
    pidx: int = 0
    partition_mask: int = 0         # >0 enables split stale-key GC in compaction
    default_ttl: int = 0            # table-level default_ttl app-env
    max_levels: int = 3             # L0 + sorted levels 1..max_levels
    target_file_size_bytes: int = 64 << 20   # split compaction output files
    level_base_bytes: int = 256 << 20        # L1 budget; Ln = base * ratio^(n-1)
    level_size_ratio: int = 10
    device_cache_bytes: int = 8 << 30  # HBM budget for resident run columns
    # device-served point reads (ISSUE 7): route get/multi_get batches
    # through the HBM-resident lookup kernels (ops/device_lookup.py)
    # under the read lane guard. None = on for backend=="tpu" unless
    # PEGASUS_DEVICE_READS=0. device_read_min_batch: smallest per-SST
    # candidate batch worth a device dispatch (below it the host binary
    # search wins; None = PEGASUS_DEVICE_READ_MIN_BATCH, default 2, so a
    # lone sequential get never pays kernel-dispatch latency).
    device_reads: bool = None
    device_read_min_batch: int = None
    # value residency: pin uniform-layout value rows in HBM alongside the
    # key columns so compaction outputs materialize on device. Off until
    # a chip run shows the download beating the host gather (ROADMAP S4).
    device_values: bool = False
    checkpoint_reserve_min_count: int = 2
    checkpoint_reserve_time_seconds: int = 0  # 0 = no time-based retention
    user_ops: tuple = ()            # parsed user-specified compaction rules
    compression: str = "none"       # SST section compression: none | zlib
    # multi-chip compaction (VERDICT-r3 item 7): when the mesh spans >1
    # device, manual_compact routes through the all_to_all hash-sharded
    # kernel (parallel.sharded_compact) instead of the single-chip merge.
    # sharded_compaction=True resolves a mesh over every visible device at
    # first use; compaction_mesh injects one explicitly (tests, dryrun).
    sharded_compaction: bool = False
    compaction_mesh: object = None  # jax.sharding.Mesh | None


@dataclass
class WriteBatch:
    """Atomic mutation set for one decree (one on_batched_write_requests)."""

    ops: list = field(default_factory=list)  # ("put", key, value, expire) | ("del", key)

    def put(self, key: bytes, value: bytes, expire_ts: int = 0):
        self.ops.append(("put", key, value, expire_ts))
        return self

    def delete(self, key: bytes):
        self.ops.append(("del", key, b"", 0))
        return self


class _RevBytes:
    """bytes wrapper with inverted ordering, for descending heap merges."""

    __slots__ = ("k",)

    def __init__(self, k: bytes):
        self.k = k

    def __lt__(self, other):
        return self.k > other.k

    def __eq__(self, other):
        return self.k == other.k


class _HbmGauges:
    """Process-wide HBM-residency accounting behind the
    `engine.hbm.budget_bytes` / `engine.hbm.resident_bytes` /
    `engine.hbm.resident_ssts` gauges on /metrics: each tpu-backend
    engine (one per partition) reports its budget/usage here on every
    prime/release, and the gauges publish the process sums — the numbers
    the collector/scheduler items queued behind the budget need to see.
    Leaf lock: never takes an engine lock (callers may hold theirs)."""

    def __init__(self):
        self._lock = lockrank.named_lock("engine.hbm_gauges")
        # id(engine) -> (budget, used_bytes, ssts)
        self._per_engine = {}  #: guarded_by self._lock

    def _publish_locked(self):  #: requires self._lock
        from ..runtime.perf_counters import counters

        vals = list(self._per_engine.values())
        counters.number("engine.hbm.budget_bytes").set(
            sum(v[0] for v in vals))
        counters.number("engine.hbm.resident_bytes").set(
            sum(v[1] for v in vals))
        counters.number("engine.hbm.resident_ssts").set(
            sum(v[2] for v in vals))

    def update(self, engine) -> None:
        with self._lock:
            self._per_engine[id(engine)] = (
                engine.opts.device_cache_bytes,
                engine._device_cache_used,
                engine._device_resident_ssts)
            self._publish_locked()

    def drop(self, engine) -> None:
        with self._lock:
            self._per_engine.pop(id(engine), None)
            self._publish_locked()


HBM_GAUGES = _HbmGauges()


class _SchedGate:
    """Per-node concurrent device-compaction cap (ISSUE 10): the cluster
    compaction scheduler bounds how many device merges run at once on
    one node so the TPU lane never convoys behind a burst of L0
    triggers. Elective (trigger-path) compactions defer at the cap;
    urgent/ceiling compactions and manual compacts always proceed — the
    cap shapes timing, never availability. max=0 (the default, knob
    PEGASUS_SCHED_MAX_DEVICE_COMPACT) disables the gate entirely, so the
    scheduler-off behavior is byte-identical to the pre-gate engine.
    Leaf lock: never takes an engine lock (callers hold theirs)."""

    def __init__(self):
        from ..runtime.perf_counters import counters

        self._lock = lockrank.named_lock("engine.sched_gate")
        # resolved once: enter/exit run under self._lock on every device
        # compaction, and a per-call registry lookup would nest the
        # registry lock under the gate lock each time
        self._c_running = counters.number(
            "engine.compact.sched.device_running")
        self._default = int(os.environ.get(
            "PEGASUS_SCHED_MAX_DEVICE_COMPACT", "0"))
        self._ttl_default = float(os.environ.get("PEGASUS_SCHED_TTL_S",
                                                 "30"))
        self._max = self._default      #: guarded_by self._lock
        # set caps are LEASES like the policy tokens: expiry reverts to
        # the env default, so a dead scheduler (or a one-off hand
        # delivery) can never leave a node capped forever
        self._max_expire = None        #: guarded_by self._lock
        self._running = 0              #: guarded_by self._lock

    def set_max(self, n, ttl_s: float = None) -> None:
        """Install a cap lease (ttl_s default PEGASUS_SCHED_TTL_S —
        every set expires; only the env default is permanent)."""
        with self._lock:
            changed = self._max != max(0, int(n))
            self._max = max(0, int(n))
            self._max_expire = time.monotonic() + (
                self._ttl_default if ttl_s is None else float(ttl_s))
            cap = self._max
        if changed:
            events.emit("sched.device_cap", cap=cap)

    def _max_locked(self) -> int:  #: requires self._lock
        if self._max_expire is not None \
                and time.monotonic() >= self._max_expire:
            self._max, self._max_expire = self._default, None
        return self._max

    def at_cap(self) -> bool:
        with self._lock:
            m = self._max_locked()
            return m > 0 and self._running >= m

    def enter(self) -> None:
        with self._lock:
            self._running += 1
            self._c_running.set(self._running)

    def exit(self) -> None:
        with self._lock:
            self._running -= 1
            self._c_running.set(self._running)

    def state(self) -> dict:
        with self._lock:
            return {"max": self._max_locked(), "default": self._default,
                    "running": self._running}


SCHED_GATE = _SchedGate()


def _fail(name: str):
    """FAIL_POINT_INJECT_F call-site helper: only the 'return' verb injects
    a failure; 'print' logs and continues (ADVICE r1: a print-armed point
    must not raise)."""
    fp = fail_point(name)
    if fp is None:
        return False
    verb, arg = fp
    if verb == "print":
        print(f"[fail_point] {name}: print({arg})")
        return False
    return True


class LsmEngine:
    def __init__(self, path: str, options: EngineOptions = None):
        self.path = path
        self.opts = options or EngineOptions()
        self._lock = lockrank.named_rlock("engine.lock")
        self._mem = Memtable()  #: guarded_by self._lock
        # immutable memtables pending flush, newest first
        self._imm = []          #: guarded_by self._lock
        # list[SSTable], newest first
        self._l0 = []           #: guarded_by self._lock
        # level(int>=1) -> list[SSTable] sorted by min_key
        self._levels = {}       #: guarded_by self._lock
        # the meta-CF equivalent (live, unflushed view)
        self._meta = {}         #: guarded_by self._lock
        self._next_file = 1     #: guarded_by self._lock
        self._last_committed_decree = 0  #: guarded_by self._lock
        self._durable_decree = 0         #: guarded_by self._lock
        # level -> round-robin cursor for cascades
        self._compact_round = {}  #: guarded_by self._lock
        # serializes checkpoint create/rename/GC (the shared checkpoint.tmp
        # dir would otherwise race between the maintenance timer and RPC
        # threads); RLock so callers can hold it across create+consume
        self.checkpoint_lock = lockrank.named_rlock("engine.checkpoint")
        # learn-shipping checkpoint pins (ISSUE 13): decree -> {lease
        # token: expiry}, one lease per active learn. A pinned decree's
        # checkpoint.{decree} dir is held out of gc_checkpoints while a
        # learner streams its blocks; pins are TTL leases renewed by
        # fetch activity, so a dead learner can never wedge GC forever
        self._ckpt_pins = {}          #: guarded_by self.checkpoint_lock
        self._pin_token = 0           #: guarded_by self.checkpoint_lock
        # decree -> cached decree-anchored digest of that checkpoint
        # (one scan per pinned checkpoint, not one per learner)
        self._ckpt_digests = {}       #: guarded_by self.checkpoint_lock
        # one flush drainer at a time
        self._flush_lock = lockrank.named_lock("engine.flush")
        # serializes compact()/_maybe_cascade()/manual_compact() merge
        # phases: two concurrent merges over overlapping input snapshots
        # would write the same records into two output sets and double-
        # unlink inputs (ADVICE r2 medium). RLock: compact -> cascade nests.
        self._compaction_lock = lockrank.named_rlock("engine.compaction")
        # tenant accounting (ISSUE 18): set by the host's set_table_name;
        # device-read probes and HBM residency charge here when wired.
        # Plain attribute write — readers tolerate None (lock-free).
        self.table_ledger = None
        # bytes of HBM pinned by resident runs
        self._device_cache_used = 0  #: guarded_by self._lock
        # files currently holding a run
        self._device_resident_ssts = 0  #: guarded_by self._lock
        # read-residency policy flag (collector hotkey loop drives it via
        # the set-read-residency remote command): hot partitions keep
        # their SSTs primed so point reads hit the device path
        self._read_hot = False  #: guarded_by self._lock
        # same-SST prime coordination (see _device_run_budgeted): waiters
        # block on this until the in-flight prime finishes and notifies
        self._prime_cv = lockrank.named_condition("engine.prime_cv",
                                                  self._lock)
        # deferred (pipelined) installs: futures for in-flight pool work,
        # consumed-input files awaiting unlink, and the manifest-write
        # debt (see _install_merge_deferred for the durability invariant)
        self._pending_installs = []  #: guarded_by self._lock
        self._pending_unlinks = []   #: guarded_by self._lock
        self._manifest_dirty = False  #: guarded_by self._lock
        # lazy sharded-compaction mesh
        self._resolved_mesh = _UNRESOLVED  #: guarded_by self._compaction_lock
        # cluster compaction scheduler (ISSUE 10): the per-partition
        # policy token the scheduler delivers over compact-sched-policy.
        # Tokens EXPIRE (ttl) back to "normal": a dead scheduler reverts
        # the engine to its local triggers, never wedges them
        self._sched_policy = "normal"  #: guarded_by self._lock
        self._sched_reasons = ()       #: guarded_by self._lock
        self._sched_expire = 0.0       #: guarded_by self._lock
        # the job-trace id riding the delivered token (ISSUE 16): the
        # compaction the token triggers adopts it, so scheduler decision
        # and engine merge share ONE timeline; cleared on adoption and
        # on lease expiry (a later local trigger mints its own id)
        self._sched_job = ""           #: guarded_by self._lock
        # compaction-offload placement (ISSUE 14): the WHERE half of the
        # scheduler's (when, where) token — a remote compaction service
        # address this cpu-only engine ships its merges to. Same lease
        # semantics as the policy token: expiry reverts to local
        # compaction, so a dead scheduler or service strands nothing
        self._offload_addr = ""        #: guarded_by self._lock
        self._offload_expire = 0.0     #: guarded_by self._lock
        # hard debt ceiling (L0 files) above which the engine-local
        # trigger ALWAYS wins, defer token or not — the availability
        # floor under any scheduler decision. 0 = 3x the L0 trigger.
        ceil = int(os.environ.get("PEGASUS_SCHED_DEBT_CEILING_FILES", "0"))
        self._sched_ceiling = ceil if ceil > 0 else max(
            1, self.opts.l0_compaction_trigger * 3)
        self._sched_ttl_s = float(os.environ.get("PEGASUS_SCHED_TTL_S",
                                                 "30"))
        # trigger-path counters resolved ONCE (the L0 gate runs on every
        # flush drain and maintenance poke — no per-call registry lookup)
        from ..runtime.perf_counters import counters
        self._c_sched_ceiling = counters.rate(
            "engine.compact.sched.ceiling_override_count")
        self._c_sched_deferred = counters.rate(
            "engine.compact.sched.deferred_count")
        self._c_sched_urgent = counters.rate(
            "engine.compact.sched.urgent_count")
        self._c_sched_gate_deferred = counters.rate(
            "engine.compact.sched.gate_deferred_count")
        self._c_offload = counters.rate("engine.compact.offload_count")
        # device-read knobs resolved ONCE (the coalescer consults them on
        # every point read — no per-get environ parse); the backend check
        # stays dynamic because app-envs can flip it at runtime
        dv = self.opts.device_reads
        self._device_reads_flag = ((os.environ.get("PEGASUS_DEVICE_READS",
                                                   "") != "0")
                                   if dv is None else bool(dv))
        mb = self.opts.device_read_min_batch
        self._device_read_min = max(1, int(
            os.environ.get("PEGASUS_DEVICE_READ_MIN_BATCH", "2"))
            if mb is None else mb)
        # corruption callout (ISSUE 17): the hosting replica stub installs
        # a callable(exc) here right after open, before the engine serves —
        # a read path or compaction hitting a CorruptionError notifies it
        # (quarantine driver) and re-raises the typed error to the caller
        self.corruption_hook = None  #: unguarded_ok set once at open, before the engine is published to serving threads
        if self.opts.backend == "tpu":
            # memoized process-wide: the first tpu-backend engine resolves
            # the platform (and refuses a silent CPU), later ones no-op
            from ..base.utils import open_device_backend

            open_device_backend()
        os.makedirs(path, exist_ok=True)
        self._load_manifest()
        if self.opts.backend == "tpu":
            HBM_GAUGES.update(self)  # budget visible before the first prime

    # ------------------------------------------------------------------ meta

    @property
    def meta_store(self) -> dict:
        return self._meta  #: unguarded_ok ref snapshot: callers get the live dict by design (reference meta-CF semantics)

    def last_durable_decree(self) -> int:
        """Decree covered by on-disk SSTs (manifest's last_flushed_decree)."""
        return int(self._durable_meta.get(META_LAST_FLUSHED_DECREE, 0))  #: unguarded_ok ref snapshot of a dict REPLACED wholesale under the lock; monotone durable watermark

    def last_committed_decree(self) -> int:
        return self._last_committed_decree  #: unguarded_ok racy read of a monotone int (gauges, decree hints)

    def data_version(self) -> int:
        return int(self._meta.get(META_DATA_VERSION, self.opts.data_version))  #: unguarded_ok data_version is written once at open

    # ----------------------------------------------------------------- write

    def write(self, batch: WriteBatch, decree: int) -> None:
        """Apply one committed batch; analogue of rocksdb_wrapper::write
        (src/server/rocksdb_wrapper.cpp:143): data ops + decree meta update,
        atomically under the engine lock."""
        if _fail("db_write"):
            raise IOError("injected db_write failure")
        rotated = False
        with self._lock:
            for op in batch.ops:
                kind, key, value, expire = op
                if kind == "put":
                    if _fail("db_write_batch_put"):
                        raise IOError("injected db_write_batch_put failure")
                    self._mem.put(key, value, expire)
                elif kind == "del":
                    if _fail("db_write_batch_delete"):
                        raise IOError("injected db_write_batch_delete failure")
                    self._mem.delete(key)
                else:
                    raise ValueError(f"unknown op {kind}")
            self._last_committed_decree = decree
            self._meta[META_LAST_FLUSHED_DECREE] = decree
            self._mem.last_decree = decree
            if self._mem.approximate_bytes >= self.opts.memtable_bytes:
                self._rotate_memtable_locked()
                rotated = True
        if rotated:
            # a full memtable must reach disk; done outside the mutation
            # loop's critical section (the reference stalls writes the same
            # way when memtables back up)
            self._drain_imms()

    def write_batch(self, pairs) -> None:
        """Apply a contiguous committed decree window — `pairs` is
        [(WriteBatch, decree)] in decree order — under ONE engine lock
        acquisition. Consecutive same-kind ops collapse into memtable
        put_batch/delete_batch calls; decree bookkeeping still advances
        per decree, so a mid-window failure (fail points) leaves
        last_committed_decree exactly at the last fully-applied decree."""
        if not pairs:
            return
        if _fail("db_write"):
            raise IOError("injected db_write failure")
        fail_put = _fail("db_write_batch_put")
        fail_del = _fail("db_write_batch_delete")
        rotated = False
        with self._lock:
            for batch, decree in pairs:
                run_kind, run = None, []
                for op in batch.ops + [None]:  # None flushes the last run
                    kind = op[0] if op is not None else None
                    if kind != run_kind and run:
                        if run_kind == "put":
                            if fail_put:
                                raise IOError(
                                    "injected db_write_batch_put failure")
                            self._mem.put_batch(run)
                        else:
                            if fail_del:
                                raise IOError(
                                    "injected db_write_batch_delete failure")
                            self._mem.delete_batch(run)
                        run = []
                    if op is None:
                        break
                    run_kind = kind
                    if kind == "put":
                        run.append((op[1], op[2], op[3]))
                    elif kind == "del":
                        run.append(op[1])
                    else:
                        raise ValueError(f"unknown op {kind}")
                self._last_committed_decree = decree
                self._meta[META_LAST_FLUSHED_DECREE] = decree
                self._mem.last_decree = decree
                if self._mem.approximate_bytes >= self.opts.memtable_bytes:
                    self._rotate_memtable_locked()
                    rotated = True
        if rotated:
            self._drain_imms()

    def put(self, key: bytes, value: bytes, expire_ts: int = 0, decree: int = None):
        d = decree if decree is not None else self._last_committed_decree + 1  #: unguarded_ok single-writer convenience path (tests/tools); replication always passes the decree
        self.write(WriteBatch().put(key, value, expire_ts), d)

    def delete(self, key: bytes, decree: int = None):
        d = decree if decree is not None else self._last_committed_decree + 1  #: unguarded_ok single-writer convenience path (tests/tools); replication always passes the decree
        self.write(WriteBatch().delete(key), d)

    # ------------------------------------------------------------------ read

    def get(self, key: bytes, now: int = None):
        """-> value bytes, or None (missing / deleted / expired).

        Search order = recency: memtable, immutables, L0 newest-first, then
        sorted levels (analogue of the read path in
        src/server/pegasus_server_impl.cpp:265-341 over our structure).
        Point reads prune files by key range and hashkey bloom filter
        (reference: hashkey_transform.h prefix bloom) before loading data.
        """
        if _fail("db_get"):
            raise IOError("injected db_get failure")
        now = epoch_now() if now is None else now
        h32 = np.uint32(key_hash(key) & 0xFFFFFFFF)
        with self._lock:
            hit = self._mem.get(key)
            if hit is None:
                for imm in self._imm:
                    hit = imm.get(key)
                    if hit is not None:
                        break
            sources = list(self._l0)
            levels = {lv: list(fs) for lv, fs in self._levels.items()}
        if hit is not None:
            value, expire, deleted = hit
            if deleted or check_if_ts_expired(now, expire):
                return None
            return value
        # the SAME recency walk get_batch's host fallback runs (one copy
        # of the ordering/pruning rules); a lone get stays host-served —
        # device batches enter through get_batch
        res = self._walk_sources([key], [now], [h32], [0], sources, levels,
                                 use_device=False)
        return res.get(0)

    @staticmethod
    def _record_or_none(block: KVBlock, i: int, now: int):
        if block.deleted[i] or check_if_ts_expired(now, int(block.expire_ts[i])):
            return None
        return block.value(i)

    # -------------------------------------------------- device-served reads

    def _device_reads_on(self) -> bool:
        return self.opts.backend == "tpu" and self._device_reads_flag

    def set_read_residency(self, on: bool) -> None:
        """Read-residency policy hook (the collector's hotkey loop drives
        this through the set-read-residency remote command): a read-hot
        partition primes every current SST into HBM — fire-and-forget on
        the pipeline pool — and may fill its WHOLE HBM budget, where a
        cold partition's primes stop at 7/8 of it (the reserved headroom
        this pin claims; see _device_run_budgeted). Off only clears the
        flag: resident runs stay (compaction still wants them) and age
        out through the normal merge lifecycle."""
        with self._lock:
            # under the engine lock: _device_run_budgeted reads the flag
            # to size the prime budget, and an unlocked flip could let a
            # cold prime claim the reserved read-hot headroom mid-check
            # (caught by tools/analyze lock_discipline)
            self._read_hot = bool(on)
            ssts = self._all_ssts_locked() \
                if on and self.opts.backend == "tpu" else []
        for sst in ssts:
            self._prime_async(sst)

    def get_batch(self, keys, now=None) -> list:
        """Batched point lookup, semantically identical to
        [get(k) for k in keys] against one consistent snapshot. `now` is
        a scalar or a per-key list (the server's read coalescer groups
        requests that resolved their clocks independently).

        Memtable/immutable hits resolve on the host; the SST walk runs
        device-side when HBM-resident runs with indexes exist — one
        batched probe per SST (ops/device_lookup.py) under the read lane
        guard, whose fallback reruns the identical walk with host binary
        search, byte-identical by construction (both return the same row
        index into the same cached block)."""
        if _fail("db_get"):
            raise IOError("injected db_get failure")
        n = len(keys)
        if now is None:
            now = epoch_now()
        nows = list(now) if isinstance(now, (list, tuple)) else [now] * n
        from ..runtime.tracing import COMPACT_TRACER

        with COMPACT_TRACER.span("read.batch", records=n):
            return self._get_batch_impl(keys, nows)

    def _get_batch_impl(self, keys, nows) -> list:
        n = len(keys)
        out = [_UNRESOLVED] * n
        h32s = [np.uint32(key_hash(k) & 0xFFFFFFFF) for k in keys]
        with self._lock:
            for i, k in enumerate(keys):
                hit = self._mem.get(k)
                if hit is None:
                    for imm in self._imm:
                        hit = imm.get(k)
                        if hit is not None:
                            break
                if hit is not None:
                    value, expire, deleted = hit
                    out[i] = (None if deleted
                              or check_if_ts_expired(nows[i], expire)
                              else value)
            sources = list(self._l0)
            levels = {lv: list(fs) for lv, fs in self._levels.items()}
        pending = [i for i in range(n) if out[i] is _UNRESOLVED]
        if pending:
            all_ssts = sources + [f for fs in levels.values() for f in fs]
            device_ok = (self._device_reads_on()
                         and any(s.device_index is not None
                                 for s in all_ssts))

            def walk(use_device):
                return self._walk_sources(keys, nows, h32s, pending,
                                          sources, levels, use_device)

            if device_ok:
                from ..runtime.lane_guard import READ_LANE_GUARD

                res = READ_LANE_GUARD.run(lambda: walk(True),
                                          lambda: walk(False), op="read")
            else:
                res = walk(False)
            for i, v in res.items():
                out[i] = v
        return [None if v is _UNRESOLVED else v for v in out]

    def _walk_sources(self, keys, nows, h32s, pending, sources, levels,
                      use_device) -> dict:
        """Recency-ordered SST walk for a key batch over a snapshot.
        Pure function of the snapshot (no engine state mutated): the read
        lane's fallback reruns it with use_device=False and must see the
        exact same inputs. -> {key index: value | None(resolved)}."""
        res = {}
        pend = list(pending)
        for sst in sources:
            if not pend:
                break
            cand = [i for i in pend if sst.maybe_contains_hash(h32s[i])]
            self._probe_sst(sst, cand, keys, nows, res, use_device)
            pend = [i for i in pend if i not in res]
        for lv in sorted(levels):
            if not pend:
                break
            files = levels[lv]
            mins = [f.min_key for f in files]
            by_file = {}
            for i in pend:
                j = bisect.bisect_right(mins, keys[i]) - 1
                if j >= 0 and files[j].maybe_contains_hash(h32s[i]):
                    by_file.setdefault(j, []).append(i)
            for j, cand in sorted(by_file.items()):
                self._probe_sst(files[j], cand, keys, nows, res, use_device)
            pend = [i for i in pend if i not in res]
        return res

    def _notify_corruption(self, exc) -> None:
        """Best-effort callout on a typed CorruptionError: counted,
        evented, and forwarded to the hosting stub's corruption_hook
        (which pulls this replica off the serving path). Callers always
        re-raise — the client gets the typed error, never garbage."""
        from ..runtime import events
        from ..runtime.perf_counters import counters

        counters.rate("engine.corruption_count").increment()
        events.emit("engine.corruption", "error",
                    path=str(getattr(exc, "path", "")),
                    detail=str(getattr(exc, "detail", exc)))
        hook = self.corruption_hook
        if hook is not None:
            try:
                hook(exc)
            except Exception as e:  # the hook must never mask the error
                print(f"[engine] corruption hook failed: {e!r}", flush=True)

    def _probe_sst(self, sst, cand, keys, nows, res, use_device) -> None:
        """Resolve one SST's candidates into `res` (hits only — a found
        tombstone/expired record resolves to None exactly like db.get).
        Device path when the file holds an indexed resident run and the
        candidate batch is worth a dispatch; host binary search otherwise
        — identical row indexes either way."""
        if not cand:
            return
        try:
            self._probe_sst_impl(sst, cand, keys, nows, res, use_device)
        except CorruptionError as e:
            self._notify_corruption(e)
            raise

    def _probe_sst_impl(self, sst, cand, keys, nows, res, use_device) -> None:
        dr = sst.device_index if use_device else None
        if dr is not None and len(cand) >= self._device_read_min:
            from ..ops.device_lookup import lookup_batch
            from ..runtime.tracing import COMPACT_TRACER

            rows = lookup_batch(dr, [keys[i] for i in cand])
            if self.table_ledger is not None:
                self.table_ledger.charge_device_read(len(cand))
            hits = [(i, int(r)) for i, r in zip(cand, rows) if r >= 0]
            with COMPACT_TRACER.span("read.gather", records=len(hits)):
                block = sst.block()
                for i, row in hits:
                    res[i] = self._record_or_none(block, row, nows[i])
            return
        for i in cand:
            row = sst.find(keys[i])
            if row >= 0:
                res[i] = self._record_or_none(sst.block(), row, nows[i])

    def scan(self, start_key: bytes = b"", stop_key: bytes = None, now: int = None,
             include_deleted: bool = False, reverse: bool = False,
             hash32=None):
        """Merged iterator over [start_key, stop_key): yields (key, value,
        expire_ts) newest-version-wins, tombstones/expired filtered.
        reverse=True iterates the same range descending (the engine-level
        Prev() the reference's reverse multi_get uses), so a bounded reader
        sees the TAIL of the range first.

        hash32: when the whole range lives under ONE hashkey (multi_get /
        sortkey_count / hash scans), its 32-bit hashkey hash lets the file
        walk probe each SST's hashkey bloom and skip files that cannot hold
        the hashkey — the reference's prefix-bloom range pruning
        (src/server/hashkey_transform.h:31-60 + ReadOptions prefix_same_as_
        start), which min/max-key overlap alone cannot provide."""
        return self._scan_over(None, start_key, stop_key, now,
                               include_deleted, reverse, hash32)

    def _scan_snapshot(self):
        """One consistent source snapshot for a merged scan — the part of
        scan() that must hold the engine lock. snapshot-only under it: the
        old code SORTED and range-filtered the whole memtable inside, so
        concurrent scanners convoyed on the lock (BASELINE's
        4-thread-slower-than-1-thread scan). list(dict.items()) is a plain
        O(n) copy; the sort/filter runs lock-free in _scan_over."""
        with self._lock:
            mem_items = list(self._mem.items())
            imm_items = [list(imm.items()) for imm in self._imm]
            ssts = list(self._l0)
            for lv in sorted(self._levels):
                ssts.extend(self._levels[lv])
        return mem_items, imm_items, ssts

    def _scan_over(self, snap, start_key, stop_key, now,
                   include_deleted=False, reverse=False, hash32=None,
                   sst_bounds=None):
        """The merged-scan generator over a _scan_snapshot (None = take
        one lazily on first pull, preserving scan()'s generator
        semantics). `sst_bounds` ({id(sst): (lo, hi)}) injects
        pre-resolved per-SST row intervals — the device range path
        (scan_range_batch) supplies them so the IDENTICAL generator below
        yields byte-identical rows with the host binary searches elided;
        absent entries mean the SST was pruned.

        A range whose rows all lie in ONE SST (memtable and immutables
        empty over it, one SST interval non-empty) leaves as slices of
        that SST's block (_slice_rows), not through the heap: a run holds
        each key once (a flush sorts a dict with newest-wins dedup,
        compact_blocks dedups every merge, mesh and offload ones included,
        and a bulk-load ingest goes through compact_blocks), so there is
        no older version to shadow. Two or more non-empty sources take the
        k-way heap merge, whose SST sources are the same cutter with every
        row and its tombstone flag kept."""
        if snap is None:
            snap = self._scan_snapshot()
        now = epoch_now() if now is None else now
        mem_items, imm_items, ssts = snap

        def in_range(k):
            return k >= start_key and (stop_key is None or k < stop_key)

        mems = [sorted((k, v) for k, v in items if in_range(k))
                for items in [mem_items] + imm_items]
        mems = [m for m in mems if m]  # newest first, like the heap's ranks
        runs = [r for r in (self._sst_rows(s, start_key, stop_key, hash32,
                                           sst_bounds) for s in ssts)
                if r is not None]
        if not mems and len(runs) == 1:
            _C_RANGE_SLICE.increment()
            yield from _slice_rows(*runs[0], now, include_deleted, reverse)
            return
        if not mems and not runs:
            return
        _C_RANGE_MERGED.increment()

        def mem_source(snap):
            it = reversed(snap) if reverse else snap
            for k, (v, e, d) in it:
                yield k, v, e, d

        sources = [mem_source(m) for m in mems]
        sources += [_slice_rows(*r, now, True, reverse, flagged=True)
                    for r in runs]
        # recency rank = position in `sources`; lower wins for equal keys.
        # descending merges invert the key order, not the recency order.
        hk = (lambda k: _RevBytes(k)) if reverse else (lambda k: k)
        heap = []
        for rank, src in enumerate(sources):
            it = iter(src)
            first = next(it, None)
            if first is not None:
                heap.append((hk(first[0]), rank, first, it))
        heapq.heapify(heap)
        prev_key = None
        while heap:
            _, rank, rec, it = heap[0]
            k = rec[0]
            nxt = next(it, None)
            if nxt is not None:
                heapq.heapreplace(heap, (hk(nxt[0]), rank, nxt, it))
            else:
                heapq.heappop(heap)
            if k == prev_key:
                continue  # an older version of a key already emitted/skipped
            prev_key = k
            _, v, e, d = rec
            if not include_deleted:
                if d or check_if_ts_expired(now, e):
                    continue
            yield k, v, e

    def _sst_rows(self, sst, start_key, stop_key, hash32, sst_bounds):
        """One SST's part of a merged scan: -> (block, lo, hi) for a
        non-empty row interval, None for a pruned file or an empty one.
        With `sst_bounds` the interval is the one the batch resolved;
        without, the file walk prunes by min/max key and hashkey bloom and
        resolves both bounds itself (counted in read.range.host_ranges)."""
        if sst_bounds is not None:
            lohi = sst_bounds.get(id(sst))
            if lohi is None or lohi[0] >= lohi[1]:
                return None  # pruned or empty interval
            lo, hi = lohi
        else:
            if sst.n == 0:
                return None
            if stop_key is not None and sst.min_key and sst.min_key >= stop_key:
                return None
            if start_key and sst.max_key and sst.max_key < start_key:
                return None
            if hash32 is not None and not sst.maybe_contains_hash(hash32):
                return None
        try:
            b = sst.block()
        except CorruptionError as e:
            self._notify_corruption(e)
            raise
        if sst_bounds is None:
            lo = sst.lower_bound(start_key) if start_key else 0
            hi = sst.lower_bound(stop_key) if stop_key is not None else b.n
            if start_key or stop_key is not None:
                _C_RANGE_HOST_RANGES.increment()
        return (b, lo, hi) if lo < hi else None

    def scan_range_batch(self, ranges, now=None, reverse=False,
                         hash32s=None) -> list:
        """Batched bounded scans over ONE consistent snapshot: for each
        (start_key, stop_key) in `ranges` (stop None = open end), yields
        exactly what scan(start, stop) would — newest-wins / tombstone /
        TTL filtered by the same merge generator — but every indexed
        resident SST resolves its per-query lower_bound row intervals
        device-side in ONE batched kernel dispatch per SST
        (ops/device_lookup.py range_batch) under READ_LANE_GUARD, whose
        fallback recomputes the same intervals with host binary search
        over the SAME snapshot. Both paths feed identical intervals to
        the identical generator (_scan_over), so results are
        byte-identical by construction. reverse=True (and engines without
        device reads) serve entirely host-side and say so in
        read.range.{reverse_host_count,host_count}.

        `now` is a scalar or per-range list (the server's range coalescer
        groups requests that resolved their clocks independently).
        -> list of iterators, one per range, in order."""
        n = len(ranges)
        if n == 0:
            return []
        if now is None:
            now = epoch_now()
        nows = list(now) if isinstance(now, (list, tuple)) else [now] * n
        h32s = list(hash32s) if hash32s is not None else [None] * n
        _C_RANGE_BATCH.increment()
        snap = self._scan_snapshot()
        device_ok = (not reverse and self._device_reads_on()
                     and any(s.device_index is not None for s in snap[2]))
        if not device_ok:
            (_C_RANGE_REV_HOST if reverse else _C_RANGE_HOST).increment(n)
            return [_count_rows(self._scan_over(
                        snap, s, t, nows[i], False, reverse, h32s[i]))
                    for i, (s, t) in enumerate(ranges)]
        from ..runtime.lane_guard import READ_LANE_GUARD

        bounds = READ_LANE_GUARD.run(
            lambda: self._resolve_sst_bounds(snap[2], ranges, h32s, True),
            lambda: self._resolve_sst_bounds(snap[2], ranges, h32s, False),
            op="range")
        return [_count_rows(self._scan_over(snap, s, t, nows[i], False,
                                            False, h32s[i],
                                            sst_bounds=bounds[i]))
                for i, (s, t) in enumerate(ranges)]

    def _resolve_sst_bounds(self, ssts, ranges, h32s, use_device) -> list:
        """Per-(query, SST) row intervals for a range batch over a
        snapshot. Pure function of the snapshot (the read lane's fallback
        reruns it with use_device=False and must see the exact same
        inputs). -> one {id(sst): (lo, hi)} dict per query; an SST absent
        from a query's dict was pruned by exactly the host iterator's
        metadata/bloom conditions, so _scan_over skips it identically."""
        bounds = [dict() for _ in ranges]
        for sst in ssts:
            if sst.n == 0:
                continue
            cand = []
            for qi, (start_key, stop_key) in enumerate(ranges):
                if stop_key is not None and sst.min_key \
                        and sst.min_key >= stop_key:
                    continue
                if start_key and sst.max_key and sst.max_key < start_key:
                    continue
                if h32s[qi] is not None \
                        and not sst.maybe_contains_hash(h32s[qi]):
                    continue
                if not start_key and stop_key is None:
                    # whole-run query: no bound to resolve on any path
                    bounds[qi][id(sst)] = (0, sst.n)
                    continue
                cand.append(qi)
            if not cand:
                continue
            dr = sst.device_index if use_device else None
            try:
                if dr is not None and len(cand) >= self._device_read_min:
                    from ..ops.device_lookup import range_batch

                    iv = range_batch(dr, [ranges[qi] for qi in cand])
                    if self.table_ledger is not None:
                        self.table_ledger.charge_device_read(len(cand))
                    for qi, (lo, hi) in zip(cand, iv):
                        bounds[qi][id(sst)] = (int(lo), int(hi))
                    continue
                for qi in cand:
                    start_key, stop_key = ranges[qi]
                    lo = sst.lower_bound(start_key) if start_key else 0
                    hi = sst.lower_bound(stop_key) \
                        if stop_key is not None else sst.n
                    bounds[qi][id(sst)] = (lo, hi)
                _C_RANGE_HOST_RANGES.increment(len(cand))
            except CorruptionError as e:
                self._notify_corruption(e)
                raise
        (_C_RANGE_DEVICE if use_device else _C_RANGE_HOST).increment(
            len(ranges))
        return bounds

    # ------------------------------------------------------------------ audit

    def state_digest(self, now: int = None, pmask: int = None) -> dict:
        """Order-independent digest of the LIVE logical state — the
        consistency-audit primitive (ISSUE 8). Walks memtable + immutables
        + every SST through the one merged recency iterator (scan: same
        newest-wins / tombstone / TTL rules as the read path), folding one
        crc64 per record (key, value bytes, expire_ts) into an XOR and an
        additive sum plus a count — commutative combines, so the PHYSICAL
        layout (what compacted where, which level holds what) cannot
        matter, only the logical contents can.

        Tombstones and expired records are EXCLUDED: per-replica
        compaction independently drops both, so their physical presence is
        legitimately divergent state. `now` must be the auditor-chosen
        clock (the trigger_audit mutation carries it) so every replica
        filters expiry against the same instant.

        Records the partition no longer OWNS after a split (the
        partition-version rule: ``key_hash % partition_count != pidx``,
        the same ownership split stale-key GC enforces in compaction)
        are excluded for the same reason: after a split, a replica that
        compacted has physically dropped its stale half while a sibling
        that has not compacted yet still holds it — comparing them would
        fake a mismatch — and the cross-CLUSTER table fold (ISSUE 11)
        would double-count every key still physically present in both
        the parent and the child partition. `pmask` must be the
        AUDITOR-chosen mask carried in the trigger-audit mutation (the
        env-spread partition_version lands at different times per
        replica; None falls back to the engine's own mask for direct
        engine-level callers)."""
        now = epoch_now() if now is None else now
        pmask = self.opts.partition_mask if pmask is None else pmask
        xor = add = n = 0
        # records fold through the BATCHED crc64 (native slice-by-8 when
        # built; its twins are test-pinned equal to the scalar crc64): the
        # per-byte python loop costs ~0.2 ms per 1 KB record, minutes for
        # one partition of a real table, inside the apply path
        recs = []

        def fold():
            nonlocal xor, add
            lens = np.fromiter((len(r) for r in recs), np.int64, len(recs))
            offs = np.zeros(len(recs), np.int64)
            np.cumsum(lens[:-1], out=offs[1:])
            cs = crc64_batch(np.frombuffer(b"".join(recs), np.uint8),
                             offs, lens)
            xor ^= int(np.bitwise_xor.reduce(cs))
            # uint64 addition wraps: the sum mod 2^64 the digest wants
            add = (add + int(cs.sum(dtype=np.uint64))) & 0xFFFFFFFFFFFFFFFF
            recs.clear()

        for k, v, e in self.scan(now=now):
            if pmask and key_hash(k) % (pmask + 1) != self.opts.pidx:
                continue
            recs.append(struct.pack("<I", len(k)) + k
                        + struct.pack("<q", int(e)) + v)
            n += 1
            if len(recs) >= 4096:
                fold()
        if recs:
            fold()
        return {"digest": f"{xor:016x}{add:016x}", "records": n, "now": now}

    # ------------------------------------------------------------------ scrub

    def scrub(self, rate_bytes_per_s: float = None) -> dict:
        """Background integrity pass (ISSUE 17): re-verify every landed
        SST's section checksums OFF the serving path (raw file reads, no
        block materialization, no device work — lane guards untouched by
        construction) and recompute the manifest-referenced file set
        against the directory. Rate-limited to `rate_bytes_per_s` when
        set. Returns {"files", "bytes", "findings": [{"path","detail"}]}.
        Findings are returned, not acted on — the hosting stub owns the
        quarantine decision. Files that vanish mid-scan (compacted away)
        or are still landing (deferred installs) are skipped, and a
        manifest reference is only a finding while the live version still
        claims it."""
        from ..runtime.fail_points import FailPointError, inject
        from ..runtime.job_trace import JOB_TRACER
        from ..runtime.perf_counters import counters

        with self._lock:
            paths = [s.path for s in self._all_ssts_locked() if s._on_disk]
        findings = []
        errors = []
        scanned_files = scanned_bytes = 0
        t0 = time.monotonic()
        with JOB_TRACER.job("engine.scrub", path=self.path):
            with JOB_TRACER.hop("scrub.files") as attrs:
                for p in paths:
                    try:
                        inject("scrub.verify")
                        scanned_bytes += verify_sst(p)
                        scanned_files += 1
                    except FileNotFoundError:
                        continue  # compacted away mid-scan
                    except FailPointError as e:
                        # injected scrub fault (chaos): the file was NOT
                        # verified — an error to retry next cadence, never
                        # a corruption finding (a finding quarantines the
                        # replica; chaos must not nuke healthy copies)
                        errors.append({"path": p, "detail": str(e)})
                    except CorruptionError as e:
                        findings.append({"path": p, "detail": e.detail})
                    if rate_bytes_per_s and rate_bytes_per_s > 0:
                        budget_s = scanned_bytes / rate_bytes_per_s
                        lag = budget_s - (time.monotonic() - t0)
                        if lag > 0:
                            time.sleep(min(lag, 1.0))
                attrs.update(files=scanned_files, bytes=scanned_bytes,
                             findings=len(findings))
            with JOB_TRACER.hop("scrub.manifest") as attrs:
                missing = self._scrub_manifest()
                attrs.update(missing=len(missing))
                findings.extend(missing)
        counters.rate("scrub.files_count").increment(scanned_files)
        counters.rate("scrub.bytes").increment(scanned_bytes)
        if findings:
            counters.rate("scrub.corruption_count").increment(len(findings))
        return {"files": scanned_files, "bytes": scanned_bytes,
                "findings": findings, "errors": errors}

    def _scrub_manifest(self) -> list:
        """Every file the on-disk MANIFEST references must exist — unless
        the live version no longer claims it (a compaction landed between
        the disk read and the existence check)."""
        mpath = os.path.join(self.path, MANIFEST)
        try:
            with open(mpath) as f:
                m = json.load(f)
            referenced = list(m.get("l0", []))
            for fs in m.get("levels", {}).values():
                referenced.extend(fs)
        except FileNotFoundError:
            return []  # fresh dir: nothing referenced yet
        except (ValueError, KeyError, TypeError) as e:
            return [{"path": mpath, "detail": f"unparseable manifest: {e}"}]
        gone = [n for n in referenced
                if not os.path.exists(os.path.join(self.path, n))]
        if not gone:
            return []
        with self._lock:
            live = self._manifest_dict_locked()
            still = set(live["l0"])
            for fs in live["levels"].values():
                still.update(fs)
        return [{"path": os.path.join(self.path, n),
                 "detail": "manifest references missing file"}
                for n in gone if n in still]

    # ----------------------------------------------------------- flush/compact

    def flush(self) -> None:
        """Rotate the memtable and flush every immutable to an L0 SST
        (device-sorted). Synchronous; oldest-first keeps both L0 recency
        order and the durable-decree invariant. Settles the currently
        queued deferred installs (light: no compaction-lock exclusion, so
        a flush never stalls behind a whole in-flight cascade)."""
        with self._lock:
            self._rotate_memtable_locked()
        self._drain_imms()
        self._settle_installs()

    def _drain_imms(self) -> None:
        """Flush pending immutables oldest-first. The flush lock serializes
        concurrent drainers (writer threads + explicit flush calls): without
        it two threads could flush the same memtable, or a newer one could
        reach disk first and falsely advance the durable decree.

        The L0 compaction trigger fires AFTER the flush lock is released:
        lockrank caught the inversion — compaction under the flush lock
        orders flush->compaction, while batched_manual_compact flushes
        engine i+1 with engine i's compaction lock held
        (compaction->flush), a deadlock waiting for the interleaving —
        and holding the flush lock across a whole compaction convoyed
        every writer behind it anyway."""
        drained = False
        with self._flush_lock:
            while True:
                with self._lock:
                    if not self._imm:
                        break
                    imm = self._imm[-1]  # list is newest-first: take oldest
                self._flush_one(imm)
                drained = True
        if drained:
            self._maybe_trigger_l0()

    def _rotate_memtable_locked(self):  #: requires self._lock
        if len(self._mem) == 0:
            return
        self._imm.insert(0, self._mem)
        self._mem = Memtable()
        self._mem.last_decree = self._last_committed_decree

    def _flush_one(self, imm: Memtable) -> None:
        # event-listener counters (reference pegasus_event_listener.h:30-52)
        from ..runtime.perf_counters import counters

        t0 = time.perf_counter()
        block = imm.to_block()
        opts = CompactOptions(backend=self.opts.backend, prefix_u32=self.opts.prefix_u32)
        sorted_block = sort_block(block, opts)
        counters.rate("engine.flush_completed_count").increment()
        counters.percentile("engine.flush_s").set(time.perf_counter() - t0)
        with self._lock:
            name = self._alloc_file_locked()
            path = os.path.join(self.path, name)
        write_sst(path, sorted_block, {"level": 0,
                                       "last_flushed_decree": imm.last_decree},
                  compression=self.opts.compression)
        sst = SSTable(path)
        sst._block = sorted_block  # already in memory: skip the disk re-read
        # flush-time residency prime: upload the newborn run's packed
        # columns off the WRITE PATH (pipeline pool) so its first
        # compaction already reads HBM without the flush paying the
        # upload; a compaction that wins the race simply host-packs once
        self._prime_async(sst)
        with self._lock:
            self._l0.insert(0, sst)
            self._imm.remove(imm)
            # durability advances exactly to this memtable's decree: every
            # older memtable has already flushed (oldest-first), younger ones
            # hold strictly later decrees (ADVICE r1 high)
            self._durable_decree = max(self._durable_decree, imm.last_decree)
            self._write_manifest_locked()

    def _prime_async(self, sst):
        """Fire-and-forget device-residency prime on the pipeline pool.
        No future is tracked: a wedged device prime must never hang a
        drain/flush/close (the per-SST in-flight marker keeps later
        callers from stacking behind it — they simply host-pack)."""
        if self.opts.backend != "tpu":
            return
        from ..ops.pipeline import submit

        submit(self._device_run_budgeted, sst)

    def _device_run_budgeted(self, sst):
        """Prime/fetch an SST's device-resident run under the HBM budget:
        past the budget (or on a device allocation failure) the file simply
        stays host-packed — compaction falls back gracefully instead of
        OOMing the write path. Concurrency: a per-SST in-flight marker
        (under the engine lock) keeps an async prime and an inline caller
        from double-uploading one file, without serializing primes of
        DIFFERENT files or holding any lock across the device upload;
        budget accounting is settled under the lock against the retired
        flag, so a release can never subtract bytes that were not added."""
        if self.opts.backend != "tpu":
            return None
        from ..runtime.lane_guard import LANE_GUARD

        want_values = self.opts.device_values
        with self._lock:
            # same-SST coordination: if another thread is mid-prime on
            # THIS file, wait for its result instead of double-uploading
            # or returning a spurious None (a compaction racing the async
            # flush prime must still get the HBM run). Bounded: a wedged
            # prime is abandoned at the lane deadline, never stacked on.
            deadline = None
            while sst._prime_inflight:
                if deadline is None:
                    eff = LANE_GUARD.effective_deadline_s()
                    # deadline <= 0 means "deadline disabled", not "give
                    # up immediately" — wait as long as the lane would
                    bound = eff if eff and eff > 0 else 3600.0
                    deadline = time.monotonic() + bound
                self._prime_cv.wait(timeout=0.05)
                if time.monotonic() > deadline:
                    return sst._device_run
            cached = sst._device_run
            if sst._device_retired:
                return None
            if cached is not None and (not want_values
                                       or cached.val2d is not None):
                return cached
            sst._prime_inflight = True
        try:
            if LANE_GUARD.breaker_open(probe=False):
                # the breaker routes all compaction to cpu; priming HBM
                # for a device the guard has declared dead would only
                # re-wedge. probe=False: the write path must never block
                # on a half-open device probe — the next guarded
                # compaction does that
                return cached
            with self._lock:
                # read-residency priority: a partition NOT flagged
                # read-hot stops priming at 7/8 of its budget, reserving
                # headroom the hotkey loop's set-read-residency pin can
                # claim — the flag is a real input to what stays
                # resident, not just a stat
                budget = self.opts.device_cache_bytes
                if not self._read_hot:
                    budget -= budget >> 3
                if self._device_cache_used >= budget:
                    return cached  # a value-less cached run still serves
            old_bytes = cached.nbytes() if cached is not None else 0
            try:
                dr = sst.device_run(self.opts.prefix_u32,
                                    with_values=want_values)
            except Exception as e:  # device OOM / backend failure: one policy
                # breaker=False: an oversized sst OOMing its prime is
                # capacity-local, not device death — it must not flap every
                # compaction onto cpu
                LANE_GUARD.record_device_failure("device_run_prime", repr(e),
                                                 breaker=False)
                _C_PRIME_FAIL.increment()
                print(f"[engine] device-run prime failed for {sst.path}: "
                      f"{e!r}", flush=True)
                sst._device_uncacheable = True
                return None
            with self._lock:
                if sst._device_retired:
                    # an async prime lost the race against the merge that
                    # consumed this file: drop the upload, never the budget
                    sst._device_run = None
                    return None
                if dr is not None:
                    self._device_cache_used += dr.nbytes() - old_bytes
                    if not sst._device_budgeted:
                        self._device_resident_ssts += 1
                    sst._device_budgeted = True
                    HBM_GAUGES.update(self)
            return dr
        finally:
            with self._lock:
                sst._prime_inflight = False
                self._prime_cv.notify_all()

    def _release_device_run(self, sst):
        with self._lock:
            sst._device_retired = True
            if sst._device_run is not None and sst._device_budgeted:
                self._device_cache_used -= sst._device_run.nbytes()
                self._device_resident_ssts -= 1
                HBM_GAUGES.update(self)
            sst._device_budgeted = False
            sst._device_run = None

    # ------------------------------------------------- compaction scheduling

    def set_compact_policy(self, policy: str, reasons=(),
                           ttl_s: float = None, job: str = "") -> None:
        """Install the cluster scheduler's per-partition policy token
        (ISSUE 10): 'defer' holds the elective L0 trigger (below the hard
        debt ceiling), 'urgent' fires it at half the normal threshold and
        lets manual compactions jump the concurrency queue, 'normal' is
        the engine-local behavior. The token expires after ttl_s (default
        PEGASUS_SCHED_TTL_S) back to 'normal' — a dead scheduler can
        never wedge compaction."""
        if policy not in ("defer", "normal", "urgent"):
            raise ValueError(f"bad compaction policy {policy!r}")
        with self._lock:
            changed = self._sched_policy != policy
            self._sched_policy = policy
            self._sched_reasons = tuple(reasons)
            self._sched_expire = time.monotonic() + (
                self._sched_ttl_s if ttl_s is None else float(ttl_s))
            if job:
                self._sched_job = job
        if changed:
            # transitions only: steady-state re-deliveries every tick
            # would be ring noise, a defer->urgent flip is the story
            events.emit("sched.token_apply", policy=policy,
                        reasons=",".join(reasons), engine=self.path)

    def compact_policy(self) -> tuple:
        """-> (policy, reasons, expires_in_s); an expired token reads —
        and resets — as ('normal', [], 0.0)."""
        expired = None
        with self._lock:
            now = time.monotonic()
            if self._sched_policy != "normal" and now >= self._sched_expire:
                expired = self._sched_policy
                self._sched_policy, self._sched_reasons = "normal", ()
                self._sched_job = ""
            out = (self._sched_policy, list(self._sched_reasons),
                   max(0.0, self._sched_expire - now)
                   if self._sched_policy != "normal" else 0.0)
        if expired is not None:
            # a lease running out (vs being replaced) means the scheduler
            # stopped delivering — exactly the kind of transient the
            # flight recorder exists to keep
            events.emit("sched.token_expired", severity="warn",
                        was=expired, engine=self.path)
        return out

    def set_offload_target(self, addr: str, ttl_s: float = None) -> None:
        """Install the scheduler's compaction-offload placement (ISSUE
        14) — the WHERE half of the (when, where) token: while the lease
        is live, this engine's merges ship to the compaction service at
        `addr` (empty = compact locally). A lapsed lease reverts to
        local compaction — a dead scheduler can never strand merges on
        a gone service (and the offload lane guard's cpu fallback covers
        the window where the lease outlives the service)."""
        with self._lock:
            changed = self._offload_addr != (addr or "")
            self._offload_addr = addr or ""
            self._offload_expire = time.monotonic() + (
                self._sched_ttl_s if ttl_s is None else float(ttl_s))
        if changed:
            events.emit("offload.placement", engine=self.path,
                        service=addr or "")

    def offload_target(self):
        """The live placement address, or None (none set / lease
        lapsed)."""
        with self._lock:
            if not self._offload_addr:
                return None
            if time.monotonic() >= self._offload_expire:
                self._offload_addr = ""
                return None
            return self._offload_addr

    def compact_policy_fast(self) -> str:
        """Lock-free policy peek for the per-write admission path (the
        debt throttle keys its slope on whether a defer token is
        deliberately accumulating this debt). Expiry is NOT checked: a
        just-lapsed defer reads as defer until the next trigger-path
        compact_policy() call resets it — at most one extra lenient
        admission window, never a correctness issue."""
        return self._sched_policy  #: unguarded_ok racy admission peek of an atomically-assigned str; compact_policy() under the lock is authoritative

    def compaction_debt(self) -> dict:
        """Compaction-debt fold (ISSUE 10): what the scheduler, the
        beacon gauges, db.stats() and the admission throttle all read —
        L0 file count, debt bytes (L0 bytes + every level's over-budget
        overflow, i.e. the pending-cascade work), and the deferred-
        install depth still riding the pipeline pool."""
        with self._lock:
            over = 0
            for lv in self._levels:
                if self._levels[lv]:
                    over += max(0,
                                self._level_bytes(lv) - self._level_budget(lv))
            return {"l0_files": len(self._l0),
                    "debt_bytes": sum(s.data_bytes for s in self._l0) + over,
                    "pending_installs": sum(
                        1 for f in self._pending_installs if not f.done()),
                    "ceiling_files": self._sched_ceiling}

    def compact_debt_ratio(self) -> float:
        """L0 debt as a fraction of the hard ceiling — the admission
        throttle charges this on EVERY write, so it is a deliberately
        lock-free racy read (a one-file-stale ratio only shifts a delay
        by one write)."""
        return len(self._l0) / float(self._sched_ceiling)  #: unguarded_ok racy admission gauge: len() of a list the trigger path re-snapshots under its locks

    def _traced_compact(self, trigger: str) -> dict:
        """Run compact() as ONE traced background job (ISSUE 16): the
        compaction adopts the id the scheduler's token delivered (so the
        decision, the token apply and this merge share a timeline) or
        mints a local id when the trigger is engine-local. compact() is
        synchronous through its deferred-install drain, so finishing
        here covers the job through the installed SST."""
        from ..runtime.job_trace import JOB_TRACER

        with self._lock:
            token_job, self._sched_job = self._sched_job, ""
        jid = JOB_TRACER.begin("compact", job_id=token_job or None,
                               engine=self.path, pidx=self.opts.pidx)
        JOB_TRACER.note("engine.trigger", job_id=jid, trigger=trigger,
                        l0_files=len(self._l0))  #: unguarded_ok trace attr snapshot; compact() re-snapshots under its locks
        try:
            with JOB_TRACER.adopt(jid):
                stats = self.compact()
        except BaseException:
            JOB_TRACER.finish(jid, status="error")
            raise
        JOB_TRACER.finish(jid,
                          input_records=stats.get("input_records", 0),
                          output_records=stats.get("output_records", 0))
        return stats

    def _maybe_trigger_l0(self) -> bool:
        """Post-flush/ingest L0 trigger behind the scheduler gate
        (ISSUE 10). With no (or an expired) policy token this is exactly
        the old `len(l0) >= trigger -> compact()` — the byte-identical
        engine-local fallback a dead scheduler degrades to. A 'defer'
        token holds the elective trigger until the hard debt ceiling,
        where the engine-local trigger always wins; an 'urgent' token
        fires at half the normal threshold; an elective trigger defers
        while the per-node device gate is at its cap. -> True when a
        compaction actually ran (poke_compaction bounds its per-tick
        work on this)."""
        l0 = len(self._l0)  #: unguarded_ok racy trigger check: compact() re-snapshots under its locks; worst case is one early/late compaction
        policy, _, _ = self.compact_policy()
        if l0 >= self._sched_ceiling:
            # availability floor: the engine-local trigger overrides any
            # defer once debt hits the ceiling (a wedged/dead scheduler
            # can never stall compaction into a write cliff)
            if policy == "defer":
                self._c_sched_ceiling.increment()
            self._traced_compact("ceiling")
            return True
        if policy == "defer":
            if l0 >= self.opts.l0_compaction_trigger:
                self._c_sched_deferred.increment()
            return False
        if policy == "urgent":
            if l0 >= max(1, self.opts.l0_compaction_trigger // 2):
                self._c_sched_urgent.increment()
                self._traced_compact("urgent")
                return True
            return False
        if l0 >= self.opts.l0_compaction_trigger:
            if self.opts.backend == "tpu" and SCHED_GATE.at_cap():
                # the node's device lanes are saturated: hold this
                # elective merge (debt stays; the next flush, the
                # maintenance poke, or the ceiling retries) instead of
                # convoying the TPU lane
                self._c_sched_gate_deferred.increment()
                return False
            self._traced_compact("trigger")
            return True
        return False

    def poke_compaction(self) -> bool:
        """Idle retry of the L0 trigger gate (the replica maintenance
        timer calls this): debt a since-expired defer token or a
        since-freed device gate left above the trigger compacts without
        waiting for the next flush — an idle engine must not carry
        trigger-level read amplification forever. -> True when a
        compaction ran (the caller limits pokes per tick so one
        synchronous merge cannot stall its siblings' maintenance)."""
        return self._maybe_trigger_l0()

    def _bottommost(self, target_level: int) -> bool:
        """Tombstones may only drop when no lower level could hold the key."""
        deeper = any(self._levels.get(lv) for lv in  #: unguarded_ok level membership only changes under the compaction lock, which every caller holds; flush only touches L0
                     range(target_level + 1, self.opts.max_levels + 1))
        return not deeper

    def compact(self, bottommost: bool = None, now: int = None) -> dict:
        """L0 compaction: merge all L0 runs with the overlapping L1 files
        into range-partitioned L1 output — the CompactRange analogue and the
        TPU seam (reference executor: src/server/pegasus_server_impl.cpp:2814).
        Cascades size-triggered single-file compactions down the levels."""
        with self._compaction_lock:
            with self._lock:
                inputs = list(self._l0)
                nonzero = [s for s in inputs if s.n]
                if not nonzero:
                    return {"input_records": 0, "output_records": 0,
                            "dropped": 0}
                lo = min(s.min_key for s in nonzero)
                hi = max(s.max_key for s in nonzero)
                overlap = self._overlapping_locked(1, lo, hi)
            bm = self._bottommost(1) if bottommost is None else bottommost
            gated = self.opts.backend == "tpu"
            if gated:  # device-compaction concurrency accounting (ISSUE 10)
                SCHED_GATE.enter()
            try:
                stats = self._merge_to_level(inputs, overlap, target_level=1,
                                             bottommost=bm, now=now,
                                             deferred=True)
                self._maybe_cascade(now)
            finally:
                if gated:
                    SCHED_GATE.exit()
            self._drain_pending_installs()
            return stats

    def _overlapping_locked(self, level: int, lo: bytes, hi: bytes):  #: requires self._lock
        out = []
        for f in self._levels.get(level, []):
            if f.n == 0 or lo is None:
                out.append(f)
            elif not (f.max_key < lo or f.min_key > hi):
                out.append(f)
        return out

    def _maybe_cascade(self, now=None):
        """While a level exceeds its byte budget, push one file (plus the
        next level's overlap) down — bounded-input leveled compaction.
        Installs are DEFERRED (pipelined): the in-memory level swap is
        immediate (so the next victim selection sees the updated sizes)
        while output k's SST write + manifest + input unlinks ride the
        pipeline pool under the merge of k+1."""
        with self._compaction_lock:
            for lv in range(1, self.opts.max_levels):
                while True:
                    with self._lock:
                        files = list(self._levels.get(lv, []))
                        if (not files
                                or self._level_bytes(lv) <= self._level_budget(lv)):
                            break
                        cursor = self._compact_round.get(lv, 0) % len(files)
                        self._compact_round[lv] = cursor + 1
                        victim = files[cursor]
                        overlap = self._overlapping_locked(
                            lv + 1, victim.min_key, victim.max_key)
                    self._merge_to_level([victim], overlap, target_level=lv + 1,
                                         bottommost=self._bottommost(lv + 1),
                                         now=now, deferred=True)
            self._drain_pending_installs()

    def _level_bytes(self, lv: int) -> int:  #: requires self._lock
        return sum(s.data_bytes for s in self._levels.get(lv, []))

    def _level_budget(self, lv: int) -> int:
        return self.opts.level_base_bytes * (self.opts.level_size_ratio ** (lv - 1))

    def _sharded_mesh(self):  #: requires self._compaction_lock
        """Mesh for multi-chip manual compaction, or None when the engine
        should stay single-chip (knob off, or <2 devices visible)."""
        if self.opts.compaction_mesh is not None:
            mesh = self.opts.compaction_mesh
            return mesh if mesh.devices.size > 1 else None
        if not self.opts.sharded_compaction or self.opts.backend != "tpu":
            return None
        if self._resolved_mesh is _UNRESOLVED:
            try:
                import jax

                from ..parallel import make_mesh

                self._resolved_mesh = (make_mesh(len(jax.devices()))
                                       if len(jax.devices()) > 1 else None)
            except Exception as e:  # no backend: stay single-chip
                from ..runtime.lane_guard import LANE_GUARD

                # breaker=False: a missing/misconfigured mesh is an
                # environment condition, not evidence the device died
                LANE_GUARD.record_device_failure("mesh_resolve", repr(e),
                                                 breaker=False)
                _C_MESH_FAIL.increment()
                print(f"[engine] sharded compaction unavailable: {e!r}",
                      flush=True)
                self._resolved_mesh = None
        return self._resolved_mesh

    def _merge_to_level(self, newer_files, older_files, target_level: int,
                        bottommost: bool, now=None, sharded: bool = False,
                        deferred: bool = False) -> dict:  #: requires self._compaction_lock
        """Merge newer_files (recency order) over older_files into
        target_level, splitting output at target_file_size_bytes.
        sharded=True (manual_compact only) routes through the multi-chip
        hash-sharded kernel when a >1-device mesh is available.
        deferred=True moves the install's disk work onto the pipeline
        pool (see _install_merge_deferred)."""
        inputs = list(newer_files) + list(older_files)
        # what is not cached loads here, one `sst_read` span a file
        input_blocks = [s.block() for s in inputs]
        mesh = self._sharded_mesh() if sharded else None
        opts = CompactOptions(
            now=now,
            pidx=self.opts.pidx,
            partition_mask=self.opts.partition_mask,
            bottommost=bottommost,
            default_ttl=self.opts.default_ttl,
            prefix_u32=self.opts.prefix_u32,
            backend=self.opts.backend,
            runs_sorted=True,
            user_ops=tuple(self.opts.user_ops),
        )
        from ..runtime.perf_counters import counters

        t0 = time.perf_counter()
        # compaction-offload placement (ISSUE 14): a cpu-only engine with
        # a live (when, where) lease ships this merge — elective trigger,
        # cascade or manual — to the rack's compaction service instead of
        # merging locally; the offload lane guard inside falls back to
        # the byte-identical local cpu merge on any service trouble
        offload_addr = (self.offload_target()
                        if mesh is None and self.opts.backend == "cpu"
                        else None)
        from ..runtime.job_trace import JOB_TRACER

        where = ("mesh" if mesh is not None
                 else "offload" if offload_addr else "local")
        with JOB_TRACER.hop("engine.merge", where=where, level=target_level,
                            inputs=len(inputs)):
            if mesh is not None:
                from ..parallel import sharded_compact_block

                result = sharded_compact_block(input_blocks, mesh, opts)
                counters.rate("engine.sharded_compaction_count").increment()
            elif offload_addr:
                from ..replication.compact_offload import offload_compact_blocks

                result = offload_compact_blocks(
                    input_blocks, opts, offload_addr,
                    tenant=f"{self.opts.pidx}@{os.path.basename(self.path)}")
                self._c_offload.increment()
            else:
                device_runs = None
                if self.opts.backend == "tpu":
                    # device-resident run cache: each SST packs+uploads once
                    # in its lifetime; this and every later compaction reads
                    # HBM directly
                    device_runs = [self._device_run_budgeted(s)
                                   for s in inputs]
                result = compact_blocks(input_blocks, opts,
                                        device_runs=device_runs)
        counters.rate("engine.compaction_completed_count").increment()
        counters.percentile("engine.compaction_s").set(time.perf_counter() - t0)
        self._install_merge_output(newer_files, older_files, result.block,
                                   target_level, deferred=deferred)
        return result.stats

    def _install_merge_output(self, newer_files, older_files, out_block,
                              target_level: int,
                              deferred: bool = False) -> None:  #: requires self._compaction_lock
        """Write + atomically swap a merge's output over its inputs —
        shared by _merge_to_level and the node-level batched compaction
        (replica_stub.batched_manual_compact). Caller holds the engine's
        compaction lock. deferred=True swaps in memory immediately and
        moves the disk work onto the pipeline pool."""
        from ..ops.pipeline import pipeline_depth
        from ..runtime.tracing import COMPACT_TRACER

        with COMPACT_TRACER.span("sst_split", records=out_block.n):
            out_blocks = _split_block(out_block,
                                      self.opts.target_file_size_bytes)
        inputs = list(newer_files) + list(older_files)
        if deferred and pipeline_depth() > 1:
            self._install_merge_deferred(inputs, out_blocks, target_level)
            return
        new_ssts = []
        for ob in out_blocks:
            with self._lock:
                path = os.path.join(self.path, self._alloc_file_locked())
            write_sst(path, ob, {"level": target_level,
                                 "last_flushed_decree": self._durable_decree},  #: unguarded_ok monotone watermark snapshot; the manifest (written under the lock) is authoritative
                      compression=self.opts.compression)
            with COMPACT_TRACER.span("sst_open"):
                sst = SSTable(path)
            sst._block = ob  # already in memory: skip the disk re-read
            # compaction output stays device-resident for its NEXT merge
            self._device_run_budgeted(sst)
            new_ssts.append(sst)
        with COMPACT_TRACER.span("manifest_write"), self._lock:
            self._swap_levels_locked(inputs, new_ssts, target_level)
            self._write_manifest_locked()
        with COMPACT_TRACER.span("sst_unlink", records=len(inputs)):
            for s in inputs:
                # keep the loaded block cached: a reader that snapshotted
                # this SSTable before we unlink must not re-read the dead
                # path (ADVICE r1 medium); the object drops with its last
                # reference. Its device columns are released NOW: the
                # budget must see the HBM back before the object's last
                # reference dies.
                self._release_device_run(s)
                try:
                    os.unlink(s.path)
                except OSError:
                    pass

    def _swap_levels_locked(self, inputs, new_ssts, target_level: int):  #: requires self._lock
        """Swap the new files in and every input file out atomically —
        inputs may come from L0 and any level (manual compact); readers
        that snapshotted before this keep their (cached) SSTables."""
        gone = set(id(f) for f in inputs)
        level = [f for f in self._levels.get(target_level, [])
                 if id(f) not in gone]
        level.extend(new_ssts)
        level.sort(key=lambda s: s.min_key or b"")
        self._levels[target_level] = level
        self._l0 = [f for f in self._l0 if id(f) not in gone]
        for lv in list(self._levels):
            if lv != target_level:
                self._levels[lv] = [f for f in self._levels[lv]
                                    if id(f) not in gone]

    def _install_merge_deferred(self, inputs, out_blocks,
                                target_level: int) -> None:  #: requires self._compaction_lock
        """Pipelined install: swap the outputs into the level structure
        NOW (in-memory SSTables serving reads from their cached blocks)
        and move the disk work — write_sst, the device-residency prime,
        the manifest write and the input unlinks — onto the pipeline
        pool, so the NEXT level's merge overlaps this output's write-out.

        Durability invariant: the on-disk manifest only ever references
        fully-written files (_write_manifest_locked defers while any live
        SST is off disk), and inputs are unlinked only after a manifest
        that no longer references them has landed. A crash inside the
        window recovers to the exact pre-merge on-disk state."""
        from ..ops.pipeline import submit_install

        meta = {"level": target_level,
                "last_flushed_decree": self._durable_decree}  #: unguarded_ok monotone watermark snapshot; the manifest (written under the lock) is authoritative
        new_ssts = []
        for ob in out_blocks:
            with self._lock:
                path = os.path.join(self.path, self._alloc_file_locked())
            new_ssts.append(SSTable.from_block(path, ob, meta))
        with self._lock:
            self._swap_levels_locked(inputs, new_ssts, target_level)
            self._manifest_dirty = True
            self._pending_unlinks.extend(inputs)
        for s in inputs:
            # HBM back under the budget before the next merge wants it
            self._release_device_run(s)
        fut = submit_install(self._deferred_install_job, new_ssts)
        with self._lock:
            self._pending_installs = [
                f for f in self._pending_installs if not f.done()]
            self._pending_installs.append(fut)

    def _deferred_install_job(self, new_ssts) -> None:
        """Pool side of a deferred install: land the output files, then
        (when every live SST is on disk) write the manifest and unlink
        the consumed inputs. Device-residency primes go back through
        _prime_async (fire-and-forget): this job must only ever block on
        DISK, so a wedged device can never hang the install drain.
        Runs under the compaction job's adopted context (the pipeline
        pool carries it), so the install hop lands in the SAME timeline
        as the trigger and merge that produced these files."""
        from ..runtime.job_trace import JOB_TRACER

        try:
            with JOB_TRACER.hop("engine.install", ssts=len(new_ssts)):
                for sst in new_ssts:
                    with self._lock:
                        if sst._device_retired:
                            # already consumed as a LATER merge's input
                            # before ever landing: its data is superseded
                            # and nothing references the path — writing it
                            # now would only recreate a file after its
                            # queued unlink ran, leaking an orphan SST
                            # forever
                            sst._on_disk = True
                            continue
                    write_sst(sst.path, sst.block(), sst.meta,
                              compression=self.opts.compression,
                              bloom=(sst.header["bloom"],
                                     sst.header["bloom_log2m"]))
                    with self._lock:
                        sst._on_disk = True
                    self._prime_async(sst)
        finally:
            self._flush_deferred_state()

    def _flush_deferred_state(self) -> None:
        """Write the deferred manifest once every live SST is on disk,
        then unlink consumed inputs it no longer references. Only inputs
        whose own install job has settled (_on_disk) unlink now — a
        consumed-before-landing output stays queued until its job marks
        it, so an in-flight write_sst can never recreate the path after
        the unlink (the job's finally re-runs this to finish the queue)."""
        unlinks = []
        with self._lock:
            if self._manifest_dirty:
                self._write_manifest_locked()
            if not self._manifest_dirty:
                unlinks = [s for s in self._pending_unlinks if s._on_disk]
                self._pending_unlinks = [
                    s for s in self._pending_unlinks if not s._on_disk]
        for s in unlinks:
            try:
                os.unlink(s.path)
            except OSError:
                pass

    def _settle_installs(self) -> None:
        """Light install settle: wait for the CURRENTLY queued install
        futures and flush the deferred manifest, without taking the
        compaction lock (no repair pass — a failed worker's rewrite
        happens in the next full drain). Used by flush(), which must not
        serialize behind an entire in-flight compaction cascade."""
        with self._lock:
            futures = list(self._pending_installs)
        for f in futures:
            f.wait()
        self._flush_deferred_state()

    def _drain_pending_installs(self) -> None:
        """Synchronize with the pipeline pool: wait for in-flight install
        jobs, synchronously rewrite any file a failed worker left
        unwritten (the manifest never referenced it — see the invariant
        in _install_merge_deferred), and flush the deferred manifest +
        unlinks. Public entry points call this so the engine's on-disk
        state is settled when they return. Runs under the compaction
        lock: install jobs are only submitted while it is held, so after
        the waits below no worker can be writing a file the repair pass
        would also write."""
        with self._compaction_lock:
            with self._lock:
                futures, self._pending_installs = self._pending_installs, []
            for f in futures:
                f.wait()
            with self._lock:
                missing = [s for s in self._all_ssts_locked()
                           if not s._on_disk]
            for s in missing:
                # repair pass: a failed deferred write retries once
                # inline; a second failure raises to the caller like a
                # synchronous install would, with the on-disk state
                # still pre-merge
                write_sst(s.path, s.block(), s.meta,
                          compression=self.opts.compression,
                          bloom=(s.header["bloom"],
                                 s.header["bloom_log2m"]))
                with self._lock:
                    s._on_disk = True
            self._flush_deferred_state()
            with self._lock:
                # no install job is in flight any more, so whatever is
                # still queued (dead consumed-before-landing outputs
                # whose job died before marking them) can go now
                leftover, self._pending_unlinks = self._pending_unlinks, []
                settled = not self._manifest_dirty
            if settled:
                for s in leftover:
                    try:
                        os.unlink(s.path)
                    except OSError:
                        pass
            else:
                with self._lock:
                    self._pending_unlinks = leftover + self._pending_unlinks

    def manual_compact(self, bottommost: bool = True, now: int = None,
                       target_level: int = None) -> dict:
        """Full compaction: everything merged into one run at target_level
        (default: the bottommost configured level). Its own traced
        "compact" job (trigger=manual) — nested under an already-active
        job this degrades to a hop, per JobTracer.job()."""
        from ..runtime.job_trace import JOB_TRACER
        with JOB_TRACER.job("compact", engine=self.path,
                            pidx=self.opts.pidx, trigger="manual"):
            return self._manual_compact_traced(bottommost, now, target_level)

    def _manual_compact_traced(self, bottommost, now, target_level) -> dict:
        from ..runtime.lane_guard import compile_wait

        # an operator asked for this compaction, off the write path: it
        # waits (bounded) for a merge program that is still compiling
        # instead of taking the host lane, as an L0 trigger would
        with compile_wait():
            return self._manual_compact_waiting(bottommost, now, target_level)

    def _manual_compact_waiting(self, bottommost, now, target_level) -> dict:
        from ..runtime.tracing import COMPACT_TRACER

        # The session records the per-stage breakdown (sst_read / pack /
        # h2d / device / gather / sst_write / manifest_write ...) into the
        # stats the manual-compact service and shell report; it spans the
        # whole call, so what no stage names is the call's own remainder.
        with COMPACT_TRACER.session() as sess:
            stats = self._manual_compact_merge(bottommost, now, target_level)
            with COMPACT_TRACER.span("manifest_write"), self._lock:
                # under the engine lock: concurrent writers update _meta's
                # decree key through write()/write_batch() (caught by
                # tools/analyze lock_discipline)
                self._meta[META_LAST_MANUAL_COMPACT_FINISH_TIME] = \
                    int(time.time())
                self._write_manifest_locked()
        if stats is None:   # nothing to merge
            return {"input_records": 0, "output_records": 0, "dropped": 0}
        return dict(stats, trace=sess.summary())

    def _manual_compact_merge(self, bottommost, now, target_level):
        """-> the merge's stats, or None when the engine holds no file."""
        self.flush()
        tl = target_level or self.opts.max_levels
        with self._compaction_lock:
            with self._lock:
                newer = list(self._l0)
                for lv in sorted(self._levels):
                    if lv < tl:
                        newer.extend(self._levels.get(lv, []))
                older = list(self._levels.get(tl, []))
            if not (newer or older):
                return None
            # inputs stay visible to readers until _merge_to_level swaps
            # the output in; a failed merge leaves the levels untouched
            gated = self.opts.backend == "tpu"
            if gated:  # device-compaction concurrency accounting
                SCHED_GATE.enter()
            try:
                return self._merge_to_level(newer, older, target_level=tl,
                                            bottommost=bottommost,
                                            now=now, sharded=True)
            finally:
                if gated:
                    SCHED_GATE.exit()

    def install_ingested_block(self, block: KVBlock) -> None:
        """Bulk-load install: a sorted, deduped block becomes a fresh L0 run
        (the IngestExternalFile seam, reference rocksdb_wrapper.cpp:185).
        Like RocksDB's default IngestExternalFile, the ingested data gets
        the NEWEST position (a fresh sequence number): it shadows any
        existing version of the same keys, at every level."""
        self.flush()  # RocksDB ingest flushes first so the fresh seqno wins
        with self._lock:
            path = os.path.join(self.path, self._alloc_file_locked())
        write_sst(path, block, {"level": 0, "ingested": True,
                                "last_flushed_decree": self._durable_decree},  #: unguarded_ok monotone watermark snapshot; the manifest (written under the lock) is authoritative
                  compression=self.opts.compression)
        with self._lock:
            self._l0.insert(0, SSTable(path))
            self._write_manifest_locked()
        self._maybe_trigger_l0()

    # ------------------------------------------------------------- checkpoint

    def checkpoint(self, dest_dir: str, flush: bool = True) -> int:
        """Hardlink-based consistent snapshot into dest_dir
        (reference: sync_checkpoint / copy_checkpoint_to_dir_unsafe,
        src/server/pegasus_server_impl.cpp:1666,1863). Returns the decree.
        flush=False snapshots only the durable state (the reference's
        async/no-flush variant)."""
        if flush:
            self.flush()
        with self._lock:
            os.makedirs(dest_dir, exist_ok=True)
            for sst in self._all_ssts_locked():
                dst = os.path.join(dest_dir, os.path.basename(sst.path))
                if os.path.exists(dst):
                    continue
                try:
                    os.link(sst.path, dst)
                except OSError:
                    if sst._block is not None:
                        # a deferred install's output that has not landed
                        # yet (or is mid-write): materialize it into the
                        # checkpoint from its cached block — the snapshot
                        # is self-contained without waiting on (or
                        # excluding) in-flight compactions
                        write_sst(dst, sst._block, sst.meta,
                                  compression=self.opts.compression,
                                  bloom=(sst.header.get("bloom", ""),
                                         sst.header.get("bloom_log2m", 0)))
                    else:
                        shutil.copy2(sst.path, dst)
            with open(os.path.join(dest_dir, MANIFEST), "w") as f:
                json.dump(self._manifest_dict_locked(), f)
            return self.last_durable_decree()

    def sync_checkpoint(self, flush: bool = True) -> int:
        """Create <path>/checkpoint.{decree}; GC old ones. Returns decree."""
        with self.checkpoint_lock:
            decree = self.checkpoint(os.path.join(
                self.path, f"{CHECKPOINT_PREFIX}tmp"), flush=flush)
            final = os.path.join(self.path, f"{CHECKPOINT_PREFIX}{decree}")
            tmp = os.path.join(self.path, f"{CHECKPOINT_PREFIX}tmp")
            if os.path.exists(final):
                shutil.rmtree(tmp)
            else:
                os.replace(tmp, final)
            self.gc_checkpoints()
            return decree

    def async_checkpoint(self):
        """Background NO-FLUSH checkpoint (the reference's async variant,
        pegasus_server_impl.cpp:1744: snapshot durable state only, never
        force a flush). Returns the Thread, or None when the latest
        checkpoint already covers the durable decree or one is running."""
        existing = self.list_checkpoints()
        if existing and existing[-1] >= self.last_durable_decree():
            return None
        if not self.checkpoint_lock.acquire(blocking=False):
            return None  # a checkpoint is already in flight
        self.checkpoint_lock.release()
        from ..runtime.tasking import spawn_thread

        t = spawn_thread(self.sync_checkpoint, flush=False, daemon=True)
        return t

    def list_checkpoints(self) -> list:
        """Sorted decrees of existing checkpoint.{decree} dirs
        (reference parse_checkpoints, pegasus_server_impl.cpp:81)."""
        out = []
        for name in os.listdir(self.path):
            if name.startswith(CHECKPOINT_PREFIX):
                suffix = name[len(CHECKPOINT_PREFIX):]
                if suffix.isdigit():
                    out.append(int(suffix))
        return sorted(out)

    def gc_checkpoints(self) -> int:
        """Drop checkpoints beyond the count/time reserves
        (reference gc_checkpoints, pegasus_server_impl.cpp:120-253)."""
        with self.checkpoint_lock:
            return self._gc_checkpoints_locked()

    def _gc_checkpoints_locked(self) -> int:
        decrees = self.list_checkpoints()
        keep_min = max(1, self.opts.checkpoint_reserve_min_count)
        dropped = 0
        now = time.time()
        pinned = self._pinned_decrees_locked()
        for d in decrees[:-keep_min] if len(decrees) > keep_min else []:
            if d in pinned:
                # an active learn streams this checkpoint's blocks
                # lock-free; dropping the dir would dangle its fetches
                continue
            cdir = os.path.join(self.path, f"{CHECKPOINT_PREFIX}{d}")
            if self.opts.checkpoint_reserve_time_seconds > 0:
                age = now - os.path.getmtime(cdir)
                if age < self.opts.checkpoint_reserve_time_seconds:
                    continue
            shutil.rmtree(cdir, ignore_errors=True)
            dropped += 1
        return dropped

    # ------------------------------------------------- learn-ship pinning

    def pin_checkpoint(self, decree: int, ttl_s: float = 600.0) -> int:
        """Hold checkpoint.{decree} out of gc_checkpoints for one learn
        (ISSUE 13). Each pin is an independent TTL LEASE identified by
        the returned token: renew/unpin act on exactly that lease, so an
        expired learner's reap can never release a LIVE learner's pin on
        the same decree. Fetch activity renews; expiry releases —
        learner death bounds the hold, not learn duration."""
        with self.checkpoint_lock:
            self._pin_token += 1
            token = self._pin_token
            self._ckpt_pins.setdefault(decree, {})[token] = \
                time.monotonic() + ttl_s
            return token

    def renew_checkpoint_pin(self, decree: int, token: int,
                             ttl_s: float) -> None:
        with self.checkpoint_lock:
            pins = self._ckpt_pins.get(decree)
            if pins and token in pins:
                pins[token] = time.monotonic() + ttl_s

    def unpin_checkpoint(self, decree: int, token: int) -> None:
        with self.checkpoint_lock:
            pins = self._ckpt_pins.get(decree)
            if pins:
                pins.pop(token, None)
            if not pins:
                self._ckpt_pins.pop(decree, None)
                self._ckpt_digests.pop(decree, None)

    def _pinned_decrees_locked(self) -> set:  #: requires self.checkpoint_lock
        now = time.monotonic()
        for d in list(self._ckpt_pins):
            live = {t: e for t, e in self._ckpt_pins[d].items() if e > now}
            if live:
                self._ckpt_pins[d] = live
            else:
                self._ckpt_pins.pop(d)
                self._ckpt_digests.pop(d, None)
        return set(self._ckpt_pins)

    def pinned_checkpoints(self) -> dict:
        """{decree: active pin count} (learn-status surface)."""
        with self.checkpoint_lock:
            self._pinned_decrees_locked()
            return {d: len(p) for d, p in self._ckpt_pins.items()}

    def checkpoint_digest(self, decree: int) -> dict:
        """Decree-anchored digest of checkpoint.{decree}'s contents (the
        PR 8 state_digest fold over a read-only engine opened on the
        checkpoint dir) — what a shipped replica must reproduce from its
        staged blocks before swapping them in. Cached per decree, with
        the TTL `now` anchor and ownership mask chosen at first
        computation, so every learner of one checkpoint compares against
        the same instant. Caller must hold a pin (the dir must not GC
        mid-scan)."""
        from ..base.utils import epoch_now

        with self.checkpoint_lock:
            hit = self._ckpt_digests.get(decree)
            if hit is not None:
                return dict(hit)
            cdir = self.get_checkpoint_dir(decree)
        # the scan runs OUTSIDE the checkpoint lock: a multi-second fold
        # must not stall the maintenance timer's sync_checkpoint. Racing
        # computers produce byte-identical folds apart from the `now`
        # anchor; setdefault keeps whichever landed first coherent.
        ver = LsmEngine(cdir, EngineOptions(
            backend="cpu", pidx=self.opts.pidx,
            prefix_u32=self.opts.prefix_u32))
        try:
            d = ver.state_digest(now=epoch_now(),
                                 pmask=self.opts.partition_mask)
        finally:
            ver.close()
        entry = {"digest": d["digest"], "records": d["records"],
                 "now": d["now"], "pmask": self.opts.partition_mask}
        with self.checkpoint_lock:
            return dict(self._ckpt_digests.setdefault(decree, entry))

    def get_checkpoint_dir(self, decree: int = None) -> str:
        """Latest (or specific) checkpoint dir for learner shipping
        (reference get_checkpoint, pegasus_server_impl.cpp:1941)."""
        decrees = self.list_checkpoints()
        if not decrees:
            raise FileNotFoundError("no checkpoints")
        d = decree if decree is not None else decrees[-1]
        return os.path.join(self.path, f"{CHECKPOINT_PREFIX}{d}")

    @classmethod
    def apply_checkpoint(cls, checkpoint_dir: str, dest_path: str,
                         options: "EngineOptions" = None) -> "LsmEngine":
        """Replace dest_path's data with the checkpoint and open it
        (reference storage_apply_checkpoint, pegasus_server_impl.cpp:1970)."""
        if os.path.exists(dest_path):
            shutil.rmtree(dest_path)
        os.makedirs(dest_path)
        for name in os.listdir(checkpoint_dir):
            src = os.path.join(checkpoint_dir, name)
            if os.path.isfile(src):
                try:
                    os.link(src, os.path.join(dest_path, name))
                except OSError:
                    shutil.copy2(src, os.path.join(dest_path, name))
        return cls(dest_path, options)

    # -------------------------------------------------------------- manifest

    def _all_ssts_locked(self):  #: requires self._lock
        out = list(self._l0)
        for lv in sorted(self._levels):
            out.extend(self._levels[lv])
        return out

    def _alloc_file_locked(self) -> str:  #: requires self._lock
        name = f"{self._next_file:06d}.sst"
        self._next_file += 1
        return name

    def _manifest_dict_locked(self) -> dict:  #: requires self._lock
        meta = {k: v for k, v in self._meta.items()}
        meta[META_LAST_FLUSHED_DECREE] = self._durable_decree
        return {
            "next_file": self._next_file,
            "l0": [os.path.basename(s.path) for s in self._l0],
            "levels": {str(lv): [os.path.basename(s.path) for s in fs]
                       for lv, fs in self._levels.items()},
            "meta": meta,
        }

    def _write_manifest_locked(self):  #: requires self._lock
        if any(not s._on_disk for s in self._all_ssts_locked()):
            # deferred installs in flight: the manifest must never
            # reference a file that has not fully landed — the last
            # completing install job (or a drain) writes it
            self._manifest_dirty = True
            return
        data = self._manifest_dict_locked()
        tmp = os.path.join(self.path, MANIFEST + ".tmp")
        with open(tmp, "w") as f:
            json.dump(data, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.path, MANIFEST))
        self._manifest_dirty = False  # only after the replace landed
        self._durable_meta = dict(data["meta"])  #: guarded_by self._lock

    def _load_manifest(self):  #: unguarded_ok construction-time: called only from __init__, before the engine is published to any other thread
        mpath = os.path.join(self.path, MANIFEST)
        if not os.path.exists(mpath):
            self._meta = {META_DATA_VERSION: self.opts.data_version}
            self._durable_meta = {}
            # repair path: adopt orphan SSTs (a replica dir from another
            # build / a manifest lost to a crash) into their header level,
            # newest file id first — the upgrade tier's "new server opens an
            # old dir" requirement (reference: rocksdb repair semantics)
            orphans = sorted(f for f in os.listdir(self.path)
                             if f.endswith(".sst"))
            for fname in orphans:
                try:
                    sst = SSTable(os.path.join(self.path, fname))
                except (ValueError, KeyError, OSError) as e:
                    print(f"[engine] skipping unreadable orphan {fname}: "
                          f"{e!r}", flush=True)
                    continue
                lv = int(sst.meta.get("level", 0))
                if lv <= 0:
                    self._l0.insert(0, sst)
                else:
                    self._levels.setdefault(lv, []).append(sst)
                self._durable_decree = max(
                    self._durable_decree,
                    int(sst.meta.get("last_flushed_decree", 0)))
                num = os.path.splitext(fname)[0]
                if num.isdigit():
                    self._next_file = max(self._next_file, int(num) + 1)
            if orphans:
                for lv in self._levels:
                    self._levels[lv].sort(key=lambda s: s.min_key or b"")
                self._meta[META_LAST_FLUSHED_DECREE] = self._durable_decree
                self._last_committed_decree = self._durable_decree
            self._write_manifest_locked()
            return
        with open(mpath) as f:
            m = json.load(f)
        self._next_file = m["next_file"]
        self._l0 = [SSTable(os.path.join(self.path, n)) for n in m["l0"]]
        self._levels = {int(lv): [SSTable(os.path.join(self.path, n)) for n in fs]
                        for lv, fs in m["levels"].items()}
        self._meta = dict(m["meta"])
        self._durable_meta = dict(m["meta"])
        self._durable_decree = int(self._meta.get(META_LAST_FLUSHED_DECREE, 0))
        self._last_committed_decree = self._durable_decree
        self._mem.last_decree = self._last_committed_decree

    def close(self):
        self._drain_pending_installs()
        HBM_GAUGES.drop(self)

    # ------------------------------------------------------------- statistics

    def device_resident_bytes(self) -> int:
        """HBM bytes pinned by this engine's resident runs — a lock-free
        racy read for attribution paths (beacon refresh, ISSUE 18) that
        must never take the engine lock."""
        return self._device_cache_used  #: unguarded_ok racy gauge read

    def stats(self) -> dict:
        with self._lock:
            debt = self.compaction_debt()  # RLock: nested re-acquire
            policy, reasons, _ = self.compact_policy()
            return {
                "compact_debt_bytes": debt["debt_bytes"],
                "pending_installs": debt["pending_installs"],
                "compact_ceiling_files": debt["ceiling_files"],
                "compact_policy": policy,
                "compact_policy_reasons": reasons,
                "compact_offload": self._offload_addr,
                "memtable_records": len(self._mem),
                "memtable_bytes": self._mem.approximate_bytes,
                "immutable_memtables": len(self._imm),
                "l0_files": len(self._l0),
                "level_files": {lv: len(fs) for lv, fs in self._levels.items() if fs},
                "level_bytes": {lv: self._level_bytes(lv)
                                for lv in self._levels if self._levels[lv]},
                "total_sst_records": sum(s.n for s in self._all_ssts_locked()),
                "last_committed_decree": self._last_committed_decree,
                "last_durable_decree": self.last_durable_decree(),
                "device_resident_bytes": self._device_cache_used,
                "device_resident_ssts": self._device_resident_ssts,
                "read_hot": self._read_hot,
            }


def _split_block(block: KVBlock, target_bytes: int) -> list:
    """Split a sorted block into chunks of ~target_bytes (key+value arenas),
    preserving order; every output chunk holds a disjoint key range.

    The chunks are VIEWS of the block: arenas and columns sliced, only the
    offset columns rebased (8 B a row, not the record), and the cuts are
    found on the offsets themselves. That needs arenas that hold the rows
    back to back in row order, which every constructor emits (block.py
    uniform_layout's precondition) and one subtraction checks; a block
    with gaps is cut by copying, as before. A chunk keeps the whole
    block's memory alive, as the copies together did."""
    n = block.n
    if n == 0:
        return [block]
    key_total, val_total = block.key_bytes_total, block.val_bytes_total
    total = key_total + val_total
    if total <= target_bytes:
        return [block]
    k0, v0 = int(block.key_off[0]), int(block.val_off[0])
    dense = (int(block.key_off[-1]) + int(block.key_len[-1]) - k0 == key_total
             and int(block.val_off[-1]) + int(block.val_len[-1]) - v0
             == val_total)
    if dense:
        def bytes_through(i: int) -> int:   # rows [0, i]: where row i+1 starts
            return total if i == n - 1 else (
                int(block.key_off[i + 1]) - k0
                + int(block.val_off[i + 1]) - v0)
    else:
        cum = np.cumsum(block.key_len.astype(np.int64)
                        + block.val_len.astype(np.int64))

        def bytes_through(i: int) -> int:
            return int(cum[i])
    bounds = []
    start = 0
    base = 0
    for _ in range(int(total // target_bytes) + 1):
        # first row whose running total reaches the target, inclusive
        cut = bisect.bisect_left(range(n), base + target_bytes,
                                 key=bytes_through) + 1
        cut = min(cut, n)
        if cut <= start:
            cut = start + 1
        bounds.append((start, cut))
        if cut >= n:
            break
        start = cut
        base = bytes_through(cut - 1)
    if not dense:
        return [block.gather(np.arange(s, e, dtype=np.int64))
                for s, e in bounds]

    def arena_rows(arena, off, ln, s, e):
        lo, hi = int(off[s]), int(off[e - 1]) + int(ln[e - 1])
        return arena[lo:hi], (off[s:e] - lo if lo else off[s:e]), ln[s:e]

    return [KVBlock(*arena_rows(block.key_arena, block.key_off,
                                block.key_len, s, e),
                    *arena_rows(block.val_arena, block.val_off,
                                block.val_len, s, e),
                    block.expire_ts[s:e], block.hash32[s:e],
                    block.deleted[s:e])
            for s, e in bounds]
