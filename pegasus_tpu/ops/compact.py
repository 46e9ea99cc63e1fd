"""Sort / k-way merge / filter: the compaction_backend={cpu,tpu} kernels.

This is the TPU seam of the whole build (SURVEY.md §2.3, BASELINE.json): the
work RocksDB does record-at-a-time inside CompactRange — comparator sort,
level merge, TTL/version dedup filtering (reference:
src/server/key_ttl_compaction_filter.h:36-115, manual compact executor
src/server/pegasus_server_impl.cpp:2814) — runs here as batched kernels
over KVBlock columns:

  1. k-way merge of already-sorted runs into full byte order of stored
     keys, newest run first within equal keys. Compaction inputs are
     sorted (SSTs are written sorted), so both backends merge — they do
     not re-sort: the CPU backend computes the merge permutation with
     vectorized binary search (np.searchsorted per run pair), the TPU
     backend with log2(n)-stage bitonic merge networks (ops.device_sort).
  2. dedup: keep only the first (= newest) version of each key;
  3. filter: drop expired-TTL records, tombstones at the bottommost level,
     and keys no longer owned by this partition after a split.

Both backends implement identical semantics on the same total order, so
output SSTs are byte-stable across cpu/tpu — the determinism requirement
that lets learner checksums and backup digests agree (SURVEY.md §7 hard
part d). tests/test_compact_ops.py asserts byte equality; the
`compact10m.fill_compact` cell (benchmarks/run.py) holds the device
lane's output to a plain reference at 10M records.

The kernels return the survivor indices (into the concatenated input) in
sorted order. Variable-length key/value bytes never touch the device: the
host gathers arenas by those indices when writing the output SST.

Uniqueness contract: within one run, keys are unique (LSM invariant — a
memtable is a map, an SST is a deduped flush/compaction output). Across
runs, duplicates are expected and resolved newest-run-first.
"""

import functools
from dataclasses import dataclass, field

import numpy as np

from ..base.utils import epoch_now
from ..engine.block import KVBlock
from ..runtime.fail_points import inject as _inject
from ..runtime.perf_counters import counters as _counters
from ..runtime.tracing import COMPACT_TRACER as _TRACE
from .kernel import DeviceKernel
from .packing import (DEFAULT_PREFIX_U32, compute_suffix_ranks,
                      pack_key_prefixes, pack_sbytes, window_lanes)

_U32_MAX = np.uint32(0xFFFFFFFF)
_MIN_BUCKET = 256  # pad runs to pow2 buckets >= this to bound jit recompiles

# monotonic total of runs refused HBM residency for a key over the prefix
# window (device-health's `bypass` block)
_C_LONG_KEY_BYPASS = _counters.number("engine.hbm.long_key_bypass_count")
# monotonic totals, one a survivor gather: by pointer arithmetic over the
# runs as they are, or (layouts not uniform) over a KVBlock.concat copy
_C_GATHER_BY_RUN = _counters.number("compact.gather.by_run_count")
_C_GATHER_CONCAT = _counters.number("compact.gather.concat_count")


@dataclass
class CompactOptions:
    now: int = None                # epoch (2016-based) seconds; default wall clock
    pidx: int = 0                  # this partition's index
    partition_mask: int = 0        # partition_version mask; 0 = no split GC
    bottommost: bool = True        # tombstones may be dropped only at bottom
    filter: bool = True            # False = flush path (pure sort, no drops)
    default_ttl: int = 0           # table-level default_ttl app-env (seconds)
    prefix_u32: int = DEFAULT_PREFIX_U32   # max prefix window, in u32 lanes
    backend: str = "cpu"           # "cpu" | "tpu"
    runs_sorted: bool = None       # None = detect; True skips the host check
    user_ops: tuple = ()           # parsed engine.compaction_rules Operations

    # device merges bigger than this split into disjoint key ranges that
    # compact independently (the bigger-than-HBM blockwise path, SURVEY
    # §5.7 long-context analogue). Sized so sort columns + aux + merge
    # temporaries of one range fit comfortably in 16 GB HBM.
    max_device_records: int = 128 << 20

    def resolved_now(self) -> int:
        return epoch_now() if self.now is None else self.now


@dataclass
class CompactResult:
    block: KVBlock
    stats: dict = field(default_factory=dict)


def _pow2ceil(n: int, floor: int = 1) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


@dataclass
class PackedRuns:
    """Host-side packed state for one compaction: per-run fixed-width sort
    columns plus the concatenated auxiliary columns the filters need.
    Runs are newest-first; each run is ascending by key after packing
    (unsorted inputs are locally argsorted here, remapping gidx)."""

    w: int                      # prefix lanes actually used
    has_rank: bool
    cols: list                  # per run: list of w uint32[n_i] prefix cols
    rank: list                  # per run: uint32[n_i] or None
    klen: list                  # per run: uint32[n_i]
    gidx: list                  # per run: int32[n_i] global concat index
    sbytes: list                # per run: S-dtype[n_i] (lazy; may hold None)
    lens: tuple                 # per run real lengths
    blocks: list                # the source KVBlocks (for lazy global aux)
    run_aux: list               # per run: (expire, deleted, hash32) in ROW
                                # order — lets the device fold the TTL/
                                # stale/tomb filter elementwise before the
                                # merge instead of gathering by gidx after

    # global-index-order aux, built lazily: only the CPU backend's
    # post-merge filter reads these; the TPU path consumes run_aux, so
    # eager concatenation would copy ~9B/record for nothing
    @property
    def expire(self) -> np.ndarray:
        if self._expire is None:
            self._expire = np.concatenate([b.expire_ts for b in self.blocks])
        return self._expire

    @property
    def deleted(self) -> np.ndarray:
        if self._deleted is None:
            self._deleted = np.concatenate([b.deleted for b in self.blocks])
        return self._deleted

    @property
    def hash32(self) -> np.ndarray:
        if self._hash32 is None:
            self._hash32 = np.concatenate([b.hash32 for b in self.blocks])
        return self._hash32

    def __post_init__(self):
        self._expire = self._deleted = self._hash32 = None


def pack_runs(runs, opts: CompactOptions, need_sbytes: bool) -> PackedRuns:
    with _TRACE.span("pack", records=sum(b.n for b in runs),
                     nbytes=sum(b.key_bytes_total + b.val_bytes_total
                                for b in runs)):
        _inject("compact.pack")
        return _pack_runs_impl(runs, opts, need_sbytes)


def _pack_runs_impl(runs, opts: CompactOptions, need_sbytes: bool) -> PackedRuns:
    max_klen = max(int(b.key_len.max()) for b in runs)
    if max_klen >= 1 << 24:
        raise ValueError("keys >= 16MiB unsupported")
    w = window_lanes(max_klen, opts.prefix_u32)
    has_rank = max_klen > 4 * w
    ranks_all = None
    if has_rank:
        concat = KVBlock.concat(runs)
        ranks_all = compute_suffix_ranks(concat, w)
    offsets = np.cumsum([0] + [b.n for b in runs])
    cols, rank_l, klen_l, gidx_l, sb_l, aux_l = [], [], [], [], [], []
    sorted_known = bool(opts.runs_sorted)
    for i, b in enumerate(runs):
        pref = pack_key_prefixes(b.key_arena, b.key_off, b.key_len, w)
        kl = b.key_len.astype(np.uint32)
        rk = ranks_all[offsets[i] : offsets[i + 1]] if has_rank else None
        gi = np.arange(offsets[i], offsets[i + 1], dtype=np.int32)
        ex, de, hs = b.expire_ts, b.deleted, b.hash32
        sb = None
        if need_sbytes or not sorted_known:
            sb = pack_sbytes([pref[:, j] for j in range(w)], kl, rk)
            if not sorted_known and not _is_sorted(sb):
                order = np.argsort(sb, kind="stable")
                pref, kl, gi, sb = pref[order], kl[order], gi[order], sb[order]
                ex, de, hs = ex[order], de[order], hs[order]
                if rk is not None:
                    rk = rk[order]
        # LSM runs are intra-run UNIQUE (flush dedups, compaction outputs
        # dedup, ingest requires dedup); inputs that violate that (tests,
        # raw external sets) get first-wins dedup HERE, on EVERY backend —
        # the device merge networks are not stable, so duplicate
        # (key, prio) rows would survive nondeterministically. Sorted runs
        # have duplicates adjacent, so the check is one vector compare
        # (over sbytes when packed, else over the raw sort columns).
        n_run = len(kl)
        dup = np.zeros(n_run, dtype=bool)
        if sb is not None:
            dup[1:] = sb[1:] == sb[:-1]
        elif n_run > 1:
            same = np.all(pref[1:] == pref[:-1], axis=1) & (kl[1:] == kl[:-1])
            if rk is not None:
                same &= rk[1:] == rk[:-1]
            dup[1:] = same
        if dup.any():
            keep_rows = ~dup
            pref, kl, gi = pref[keep_rows], kl[keep_rows], gi[keep_rows]
            ex, de, hs = ex[keep_rows], de[keep_rows], hs[keep_rows]
            if sb is not None:
                sb = sb[keep_rows]
            if rk is not None:
                rk = rk[keep_rows]
        cols.append([np.ascontiguousarray(pref[:, j]) for j in range(w)])
        rank_l.append(rk)
        klen_l.append(kl)
        gidx_l.append(gi)
        sb_l.append(sb)
        aux_l.append((ex, de, hs))
    return PackedRuns(
        w=w, has_rank=has_rank, cols=cols, rank=rank_l, klen=klen_l,
        gidx=gidx_l, sbytes=sb_l,
        # post-dedup lengths (gidx still indexes the ORIGINAL concat)
        lens=tuple(len(g) for g in gidx_l),
        blocks=list(runs), run_aux=aux_l,
    )


def _is_sorted(sb: np.ndarray) -> bool:
    return bool(np.all(sb[1:] >= sb[:-1])) if len(sb) > 1 else True


def _filter_keep(keep, gidx, packed: PackedRuns, now, pidx, pmask, bottommost):
    expire = packed.expire[gidx]
    keep &= ~((expire > 0) & (expire <= now))
    if pmask:
        keep &= (packed.hash32[gidx] & np.uint32(pmask)) == np.uint32(pidx)
    if bottommost:
        keep &= ~packed.deleted[gidx]
    return keep


class CpuBackend:
    """Vectorized numpy merge — the honest CPU baseline for bench. Exploits
    run-sortedness exactly like RocksDB's heap merge does, but batched:
    each record's merged rank = own position + count of smaller records in
    every other run (binary search), then a scatter materializes the merge.
    """

    name = "cpu"

    def survivors(self, packed: PackedRuns, now, pidx, pmask, bottommost,
                  do_filter) -> np.ndarray:
        # "device" = the merge+dedup+filter stage on whichever backend runs
        # it — same stage name as the tpu path so traces compare 1:1
        with _TRACE.span("device", records=sum(packed.lens)):
            return self._survivors(packed, now, pidx, pmask, bottommost,
                                   do_filter)

    def _survivors(self, packed: PackedRuns, now, pidx, pmask, bottommost,
                   do_filter) -> np.ndarray:
        K = len(packed.lens)
        if K == 1:
            merged_sb, merged_gidx = packed.sbytes[0], packed.gidx[0]
        else:
            total = sum(packed.lens)
            merged_sb = np.empty(total, dtype=packed.sbytes[0].dtype)
            merged_gidx = np.empty(total, dtype=np.int32)
            from .. import native

            use_native = native.available()
            for i in range(K):
                r = np.arange(packed.lens[i], dtype=np.int64)
                for j in range(K):
                    if j == i:
                        continue
                    # equal keys order newest-run (lowest index) first
                    side = "right" if j < i else "left"
                    if use_native:
                        # galloping two-pointer pass over both sorted runs
                        r += native.merge_counts(packed.sbytes[i],
                                                 packed.sbytes[j], side)
                    else:
                        r += np.searchsorted(packed.sbytes[j], packed.sbytes[i],
                                             side=side)
                merged_sb[r] = packed.sbytes[i]
                merged_gidx[r] = packed.gidx[i]
        same = np.zeros(len(merged_sb), dtype=bool)
        same[1:] = merged_sb[1:] == merged_sb[:-1]
        keep = ~same
        if do_filter:
            keep = _filter_keep(keep, merged_gidx, packed, now, pidx, pmask,
                                bottommost)
        return merged_gidx[keep]


@dataclass
class DevicePacked:
    """Device-resident compaction inputs. In the engine's hot path these
    live in HBM across the LSM lifecycle — uploaded once when a run is
    born (flush / previous compaction output), so compaction reads HBM,
    not PCIe (SURVEY.md §5.7c 'HBM-resident key blocks')."""

    run_cols: tuple   # per run: (w [+rank] prefix cols, klen, gidx) jax arrays
    aux: tuple        # per run: (expire, deleted, hash32) jax arrays,
                      # ROW-aligned and padded like run_cols (feeds the
                      # pre-merge filter fold; NOT concat order)
    padded_lens: tuple
    w: int
    has_rank: bool


@dataclass
class DeviceRun:
    """One run's cacheable device-resident packed columns — the engine's
    'HBM-resident key blocks' (SURVEY §5.7c): an SSTable packs + uploads
    these ONCE (flush prime or first device compaction) and every later
    compaction it joins reads HBM, not PCIe. A run's window is as wide as
    its longest key (packing.window_lanes); only runs with a key over the
    cap (64 B: suffix-rank merges) are not cacheable: ranks are global to
    a merge set.

    EVERY column is padded to the pow2 bucket so the jitted merge is keyed
    only on (padded_lens, run widths) — real lengths travel as traced
    scalars and distinct run sizes in one bucket share one XLA program
    (the same recompile bound the host path gets from _MIN_BUCKET)."""

    cols: tuple       # w jnp.uint32 arrays, padded to padded_len (pads 0xFF)
    klen: object      # jnp.uint32[padded_len] (pads 0xFFFFFFFF)
    expire: object    # jnp.uint32[padded_len] (pads 0)
    deleted: object   # jnp.bool_[padded_len] (pads False)
    hash32: object    # jnp.uint32[padded_len] (pads 0)
    n: int
    padded_len: int
    w: int
    # value-residency extension (uniform-layout runs only): the run's value
    # rows live in HBM too, so compaction output values materialize on
    # device instead of the host arena gather (VERDICT-r3 item 3)
    val2d: object = None   # jnp.uint8[padded_len, vl0] or None
    vl0: int = 0
    # per-SST read index (ISSUE 7): fence-pointer samples of the first
    # key lane, built on device as a byproduct of this prime
    # (ops/device_lookup.py build_fence_index); None = host-served reads
    fence: object = None   # jnp.uint32[fence_len] or None
    fence_step: int = 0
    fence_len: int = 0
    # n and fence_step as the device scalars the fence build made of
    # them: every read call passes these two, made once a prime
    n_dev: object = None
    step_dev: object = None

    def nbytes(self) -> int:
        base = (len(self.cols) + 3) * 4 * self.padded_len + self.padded_len
        if self.val2d is not None:
            base += self.padded_len * self.vl0
        if self.fence is not None:
            base += 4 * self.fence_len + 8
        return base


def pack_run_device(block, prefix_u32: int = DEFAULT_PREFIX_U32,
                    with_values: bool = False):
    """-> DeviceRun, or None when this run cannot be cached (a key longer
    than the window's cap needs per-merge suffix ranks). The run must be
    sorted (SSTs are born sorted). with_values additionally pins the value
    rows in HBM when the layout is uniform (value residency)."""
    import jax.numpy as jnp

    if block.n == 0:
        return None
    max_klen = int(block.key_len.max())
    w = window_lanes(max_klen, prefix_u32)
    if max_klen > 4 * w:
        # production policy (long keys need per-merge suffix ranks), but
        # this file will never be HBM-resident nor device-read: count it
        _C_LONG_KEY_BYPASS.increment()
        return None
    padded = _pow2ceil(block.n, _MIN_BUCKET)
    # the two stages of a prime, as the host-packed lane names them:
    # every host array first, then every upload
    with _TRACE.span("pack", records=block.n):
        pref = pack_key_prefixes(block.key_arena, block.key_off,
                                 block.key_len, w)
        host_cols = [_pad_to(np.ascontiguousarray(pref[:, j]), padded)
                     for j in range(w)]
        host_klen = _pad_to(block.key_len.astype(np.uint32), padded)
        host_aux = [_zpad_to(a, padded) for a in
                    (block.expire_ts, block.deleted, block.hash32)]
        rows, vl0 = None, 0
        if with_values:
            uni = block.uniform_layout()
            if uni is not None:
                vl0 = uni[1]
                rows = np.zeros((padded, vl0), np.uint8)
                rows[: block.n] = block.val_arena.reshape(block.n, vl0)
    with _TRACE.span("h2d", records=block.n) as sp:
        cols = tuple(jnp.asarray(c) for c in host_cols)
        klen = jnp.asarray(host_klen)
        expire, deleted, hash32 = (jnp.asarray(a) for a in host_aux)
        val2d = jnp.asarray(rows) if rows is not None else None
        dr = DeviceRun(
            cols=cols, klen=klen, expire=expire, deleted=deleted,
            hash32=hash32, n=block.n, padded_len=padded, w=w, val2d=val2d,
            vl0=vl0)
        sp["bytes"] = dr.nbytes()
    # read index as a byproduct of the compaction/flush prime: the sorted
    # key column is on the chip RIGHT NOW, so the fence build is one tiny
    # device gather (CompassDB's moment to build the point-read index)
    from .device_lookup import build_fence_index

    build_fence_index(dr)
    return dr


class TpuBackend:
    """JAX device pipeline; jit-cached per (padded run lengths, width)."""

    name = "tpu"

    def survivors_cached_device(self, device_runs, now, pidx, pmask,
                                bottommost, do_filter, want_padded=False):
        """The engine hot path: merge cached DeviceRuns (newest first)
        without any host packing or re-upload. Returns the survivor index
        still ON DEVICE (+ count) so the caller can overlap its download
        with the host arena gather. want_padded additionally returns the
        padded-concat survivor index (the per-run value gather's input):
        (mapped, padded, count) instead of (mapped, count)."""
        import jax.numpy as jnp

        w = max(r.w for r in device_runs)
        lens = tuple(r.padded_len for r in device_runs)
        ws = tuple(r.w for r in device_runs)
        fn = (_compiled_pipeline_cached_padded(lens, ws, w) if want_padded
              else _compiled_pipeline_cached(lens, ws, w))
        cached = tuple(tuple(r.cols) + (r.klen,) for r in device_runs)
        aux = tuple((r.expire, r.deleted, r.hash32) for r in device_runs)
        real_lens = jnp.asarray([r.n for r in device_runs], jnp.int32)
        # the int(count) below syncs on the kernel, so the span's wall time
        # covers dispatch + device execution
        with _TRACE.span("device", records=sum(r.n for r in device_runs)):
            _inject("compact.device")
            out = fn(cached, aux, real_lens,
                     jnp.uint32(now), jnp.uint32(pidx),
                     jnp.uint32(pmask), jnp.asarray(bool(bottommost)),
                     jnp.asarray(bool(do_filter)))
            return (*out[:-1], int(out[-1]))

    def survivors_cached(self, device_runs, now, pidx, pmask, bottommost,
                         do_filter) -> np.ndarray:
        out_idx, count = self.survivors_cached_device(
            device_runs, now, pidx, pmask, bottommost, do_filter)
        return np.asarray(out_idx[:count])

    def prepare(self, packed: PackedRuns) -> DevicePacked:
        with _TRACE.span("h2d", records=sum(packed.lens)) as sp:
            _inject("compact.h2d")
            prep = self._prepare(packed)
            sp["bytes"] = sum(
                sum(int(a.size) * a.dtype.itemsize for a in rc)
                for rc in prep.run_cols)
            return prep

    def _prepare(self, packed: PackedRuns) -> DevicePacked:
        import jax.numpy as jnp

        padded_lens = tuple(_pow2ceil(n, _MIN_BUCKET) for n in packed.lens)
        run_cols = []
        aux = []
        for i in range(len(packed.lens)):
            arrays = list(packed.cols[i])
            if packed.has_rank:
                arrays.append(packed.rank[i])
            arrays.append(packed.klen[i])
            arrays.append(packed.gidx[i])
            run_cols.append(tuple(
                jnp.asarray(_pad_to(a, padded_lens[i])) for a in arrays
            ))
            # per-run ROW-aligned aux, zero-padded (pads are already
            # excluded by gidx == -1, so their filter bits are moot)
            ex, de, hs = packed.run_aux[i]
            aux.append(tuple(
                jnp.asarray(_zpad_to(a, padded_lens[i]))
                for a in (ex, de, hs)))
        return DevicePacked(tuple(run_cols), tuple(aux), padded_lens,
                            packed.w, packed.has_rank)

    def survivors_device(self, packed, now, pidx, pmask, bottommost,
                         do_filter):
        """-> (device survivor index, count): keep the index on device so
        the download can overlap the host gather."""
        import jax.numpy as jnp

        prep = packed if isinstance(packed, DevicePacked) else self.prepare(packed)
        fn = _compiled_pipeline(prep.padded_lens, prep.w, prep.has_rank)
        # int(count) syncs on the kernel: the span covers dispatch + device
        with _TRACE.span("device", records=sum(prep.padded_lens)):
            _inject("compact.device")
            out_idx, count = fn(
                prep.run_cols, prep.aux,
                jnp.uint32(now), jnp.uint32(pidx), jnp.uint32(pmask),
                jnp.asarray(bool(bottommost)), jnp.asarray(bool(do_filter)),
            )
            return out_idx, int(count)

    def survivors(self, packed, now, pidx, pmask, bottommost,
                  do_filter) -> np.ndarray:
        out_idx, count = self.survivors_device(packed, now, pidx, pmask,
                                               bottommost, do_filter)
        return np.asarray(out_idx[:count])


@dataclass
class DeviceVals:
    """Device-resident value rows for a uniform-layout block, uploaded at
    flush time like the key columns (SURVEY §7c: the host-side arena
    gather of 10M variable-length values was the 1.27s bottleneck at the
    r3 best — value rows living in HBM let survivors materialize on
    device and come back as one contiguous transfer)."""

    val2d: object  # jnp.uint8[n, vl0]
    vl0: int
    n: int

    def nbytes(self) -> int:
        return self.n * self.vl0


def prepare_values(block: KVBlock) -> "DeviceVals | None":
    """Upload a uniform-layout block's value rows to device; None when the
    layout is not uniform (variable-width values stay host-gathered)."""
    import jax.numpy as jnp

    uni = block.uniform_layout()
    if uni is None:
        return None
    _, vl0 = uni
    return DeviceVals(jnp.asarray(block.val_arena.reshape(block.n, vl0)),
                      vl0, block.n)


@functools.lru_cache(maxsize=64)
def _compiled_val_gather(n: int, vl0: int, bucket: int):
    import jax.numpy as jnp

    def fn(val2d, idx):
        # idx rows past the real count carry -1; clip to row 0 (discarded
        # by the host-side [:count] slice)
        safe = jnp.clip(idx, 0, np.int32(n - 1))
        return jnp.take(val2d, safe, axis=0)

    return DeviceKernel(fn, "val_gather")


def _finish_overlapped(runs, out_dev, real_idx, count: int,
                       kl0: int, vl0: int) -> KVBlock:
    """Shared tail of both value-residency materializers: start the value
    download, gather keys+aux by run on the host while it is in flight
    (native fused loop, numpy twin), assemble the uniform output block.
    runs: uniform-layout blocks of the one (kl0, vl0); real_idx indexes
    them as if concatenated."""
    with _TRACE.span("gather", records=count,
                     nbytes=count * (kl0 + vl0)):
        _inject("compact.gather")
        _C_GATHER_BY_RUN.increment()
        return _finish_overlapped_impl(runs, out_dev, real_idx, count,
                                       kl0, vl0)


def _finish_overlapped_impl(runs, out_dev, real_idx, count: int,
                            kl0: int, vl0: int) -> KVBlock:
    from .. import native

    try:
        out_dev.copy_to_host_async()
    except AttributeError:
        pass
    idx = np.asarray(real_idx[:count])
    out_k = np.empty((count, kl0), np.uint8)
    out_e = np.empty(count, np.uint32)
    out_h = np.empty(count, np.uint32)
    out_d = np.empty(count, np.bool_)
    native.gather_runs_uniform(runs, kl0, vl0, idx, out_k, None,
                               out_e, out_h, out_d)
    out_v = np.asarray(out_dev)[:count]
    return KVBlock.uniform(kl0, vl0, out_k, out_v, out_e, out_h, out_d)


def materialize_device_survivors(concat: KVBlock, dev_vals: DeviceVals,
                                 dev_idx, count: int) -> KVBlock:
    """Materialize the compaction output with the value rows gathered ON
    DEVICE and downloaded as one contiguous block, overlapped with the
    host-side keys+aux gather — the two halves pay max() instead of sum().
    Requires uniform layout and a resident DeviceVals; anything else falls
    back to the host-gather path."""
    if count == 0:
        return KVBlock.empty()
    uni = _shared_uniform_layout([concat])
    if uni is None or dev_vals is None or dev_vals.n != concat.n \
            or uni[1] != dev_vals.vl0:
        return gather_runs([concat], dev_idx, count)
    kl0, vl0 = uni
    bucket = min(_pow2ceil(count, 1 << 16), int(dev_idx.shape[0]))
    fn = _compiled_val_gather(dev_vals.n, vl0, bucket)
    out_dev = fn(dev_vals.val2d, dev_idx[:bucket])
    return _finish_overlapped([concat], out_dev, dev_idx, count, kl0, vl0)


def _shared_uniform_layout(runs):
    """(key_len, val_len) when every run is a uniform-layout block of the
    same widths and the rows together fit an int32 index — what lets a
    gather resolve a concat-space index to (run, row) by arithmetic; None
    otherwise (variable-width keys or values: the redis proxy's, geo's)."""
    if sum(b.n for b in runs) >= (1 << 31):
        return None
    uni = runs[0].uniform_layout()
    if uni is None or any(b.uniform_layout() != uni for b in runs[1:]):
        return None
    return uni


def gather_runs(runs, idx, count: int, chunks: int = 8) -> KVBlock:
    """KVBlock.concat(runs).gather(idx[:count]) without the concat where
    the layout allows: idx is in real-concat space (run r owns
    [starts[r], starts[r+1]), starts = cumsum of the runs' rows), so over
    uniform runs of shared widths the gather resolves each index to
    (run, row) and reads the runs where they lie — the only host copy of
    a merge's output. Anything else concatenates first, under the `concat`
    span, as before.

    idx may still be in flight on the device: it splits into chunks whose
    host copies all start asynchronously up front, so the arena gather of
    chunk i overlaps the transfer of chunks i+1.. (VERDICT-r2 item 3 — on
    this box the index download and the memcpy-bound gather are comparable
    costs; overlapped they pay max() instead of sum())."""
    if count == 0:
        return KVBlock.empty()
    # the fail point is the device lane's (survivor download +
    # materialization): the cpu lane, the guard's fallback, brings a host
    # index and must stay clear of it
    in_flight = not isinstance(idx, np.ndarray)
    uni = _shared_uniform_layout(runs)
    if uni is None:
        runs = [_concat(runs)]   # its span closes before `gather` opens
    (_C_GATHER_CONCAT if uni is None else _C_GATHER_BY_RUN).increment()
    with _TRACE.span("gather", records=count):
        if in_flight:
            _inject("compact.gather")
        if uni is None:
            return runs[0].gather(np.asarray(idx[:count]))
        return _gather_runs_impl(
            runs, idx, count,
            chunks if in_flight and count >= (1 << 16) else 1, *uni)


def _gather_runs_impl(runs, idx, count: int, chunks: int,
                      kl0: int, vl0: int) -> KVBlock:
    from .. import native

    out_k = np.empty((count, kl0), np.uint8)
    out_v = np.empty((count, vl0), np.uint8)
    out_e = np.empty(count, np.uint32)
    out_h = np.empty(count, np.uint32)
    out_d = np.empty(count, np.bool_)
    bounds = [count * i // chunks for i in range(chunks + 1)]
    parts = []
    for a, b in zip(bounds, bounds[1:]):
        if a == b:
            continue
        part = idx[a:b]
        try:
            part.copy_to_host_async()
        except AttributeError:
            pass
        parts.append((a, b, part))
    for a, b, part in parts:
        native.gather_runs_uniform(runs, kl0, vl0, np.asarray(part),
                                   out_k[a:b], out_v[a:b], out_e[a:b],
                                   out_h[a:b], out_d[a:b])
    return KVBlock.uniform(kl0, vl0, out_k, out_v, out_e, out_h, out_d)


def _pad_to(a: np.ndarray, n: int) -> np.ndarray:
    if len(a) == n:
        return a
    fill = -1 if a.dtype == np.int32 else _U32_MAX
    out = np.full(n, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


def _zpad_to(a: np.ndarray, n: int) -> np.ndarray:
    if len(a) == n:
        return a
    out = np.zeros(n, dtype=a.dtype)
    out[: len(a)] = a
    return out


def _pipeline_body(run_cols, aux_runs, padded_lens, nk, use_pallas,
                   now, pidx, pmask, bottommost, do_filter):
    """Traced merge→dedup→filter→compact body shared by both jitted entry
    points (host-packed and device-cached runs).

    Sort key per record: (w prefix lanes, [suffix rank,] klen<<8|prio).
    Pads carry 0xFFFFFFFF keys / idx -1 and sort to the tail of every
    merge; they are excluded by the idx >= 0 guard at the end.

    aux_runs holds each run's ROW-aligned (expire, deleted, hash32): the
    TTL/stale/tombstone filter folds into the idx column BEFORE the merge
    (filtered rows get idx -1, elementwise — no post-merge aux gathers,
    which cost ~0.5s at 16M on hardware). Row-equivalent to the old
    post-merge form: a key's duplicates are masked by `same` regardless of
    the newest version's filter bit, so a filtered newest still shadows
    (and drops) its older versions, exactly as before."""
    import jax
    import jax.numpy as jnp

    from .device_sort import merge_two_sorted
    from .pallas_merge import merge_two_sorted_pallas

    # the named scopes are op metadata only (a profile's device ops carry
    # them); the compile cache keys on the IR with debug info stripped
    items = []
    for i, rc in enumerate(run_cols):
        *kcols, klen, idx = rc
        expire, deleted, hash32 = aux_runs[i]
        with jax.named_scope("pegasus_filter"):
            expired = (expire > 0) & (expire <= now)
            stale = jnp.where(pmask > 0, (hash32 & pmask) != pidx, False)
            filt = expired | stale | (deleted & bottommost)
            idx = jnp.where(do_filter & filt, np.int32(-1), idx)
        with jax.named_scope("pegasus_key_build"):
            kp = (klen << jnp.uint32(8)) | jnp.uint32(i)
        items.append((padded_lens[i], list(kcols) + [kp, idx]))
    pad_fill = tuple([_U32_MAX] * nk + [np.int32(-1)])
    while len(items) > 1:
        items.sort(key=lambda t: t[0])
        (la, a), (lb, b) = items[0], items[1]
        if use_pallas:
            # tier-2 kernel: whole merge in VMEM, ~2 HBM passes
            merged = merge_two_sorted_pallas(a, b, nk, pad_fill)
        else:
            merged = merge_two_sorted(a, b, nk, pad_fill)
            lm = _pow2ceil(la + lb)
            if lm > la + lb:
                merged = [c[: la + lb] for c in merged]
        items = items[2:] + [(la + lb, merged)]
    _, cols = items[0]
    with jax.named_scope("pegasus_dedup_compact"):
        idx = cols[-1]
        kp = cols[nk - 1]
        key_eq_cols = cols[: nk - 1] + [kp >> jnp.uint32(8)]
        same_tail = functools.reduce(
            jnp.logical_and, [c[1:] == c[:-1] for c in key_eq_cols]
        )
        same = jnp.concatenate([jnp.zeros(1, dtype=bool), same_tail])
        keep = (idx >= 0) & ~same
        n = idx.shape[0]
        pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
        count = pos[-1] + 1
        tgt = jnp.where(keep, pos, n)
        out_idx = jnp.full((n,), -1, jnp.int32).at[tgt].set(idx, mode="drop")
    return out_idx, count


@functools.lru_cache(maxsize=256)
def _compiled_pipeline(padded_lens: tuple, w: int, has_rank: bool):
    """Jitted pipeline over host-packed runs (prepare() uploads)."""

    from .pallas_merge import pallas_enabled

    nk = w + (1 if has_rank else 0) + 1
    use_pallas = pallas_enabled()

    def fn(run_cols, aux, now, pidx, pmask, bottommost, do_filter):
        return _pipeline_body(run_cols, aux, padded_lens, nk, use_pallas,
                              now, pidx, pmask, bottommost, do_filter)

    return DeviceKernel(fn, "merge_packed")


def _make_cached_fn(padded_lens: tuple, run_ws: tuple, w: int,
                    allow_pallas: bool = True, want_padded: bool = False):
    """Build the (unjitted) traceable pipeline over CACHED device runs.

    Each input run arrives as its cached fully-padded device columns —
    packed+uploaded ONCE when the SST was born or first joined a device
    compaction. Everything a specific merge needs beyond that is derived
    INSIDE the trace (fused, no extra dispatches): missing prefix lanes
    for runs with shorter keys (all-zero by construction, 0xFFFFFFFF in
    the pad tail), the concat index, and the aux concatenation.

    Real run lengths are TRACED scalars, so compile caches key on
    (padded bucket lengths, run widths) only — a live engine's endlessly
    varying run sizes share programs per bucket instead of recompiling
    per compaction. Internally the merge works in PADDED-concat index
    space (aligned with the padded aux concat); the last step maps
    survivor indices back to real-concat space for the host gather.

    Used directly by _compiled_pipeline_cached (one merge) and under vmap
    by the batched multi-partition pipeline (ops.batched_compact)."""
    import jax.numpy as jnp
    from jax import lax

    from .pallas_merge import pallas_enabled

    nk = w + 1  # cached runs never carry a suffix-rank column
    use_pallas = pallas_enabled() and allow_pallas
    padded_offsets = np.cumsum([0] + list(padded_lens))

    def fn(cached_runs, aux_runs, real_lens, now, pidx, pmask, bottommost,
           do_filter):
        import jax

        run_cols = []
        for i, rc in enumerate(cached_runs):
            *kcols, klen = rc
            with jax.named_scope("pegasus_key_build"):
                iota = lax.iota(jnp.int32, padded_lens[i])
                in_run = iota < real_lens[i].astype(jnp.int32)
                # pads must keep 0xFF keys even in synthesized zero lanes,
                # and a real record whose cached klen pad says 0xFF cannot
                # occur (in_run covers exactly the packed rows)
                for _ in range(w - run_ws[i]):
                    kcols.append(jnp.where(in_run, jnp.uint32(0), _U32_MAX))
                gidx = jnp.where(in_run, iota + np.int32(padded_offsets[i]),
                                 np.int32(-1))
            run_cols.append(tuple(kcols + [klen, gidx]))
        # aux_runs are already per-run ROW-aligned padded columns — exactly
        # what the pre-merge filter fold consumes (pad rows carry zeros,
        # and their gidx is -1 regardless)
        out_idx, count = _pipeline_body(
            run_cols, aux_runs, padded_lens, nk, use_pallas,
            now, pidx, pmask, bottommost, do_filter)
        # padded-concat -> real-concat index mapping: subtract each run's
        # accumulated pad slack (static boundaries, traced deltas)
        real_off = jnp.cumsum(jnp.concatenate(
            [jnp.zeros(1, jnp.int32), real_lens.astype(jnp.int32)]))
        mapped = out_idx
        for i in range(len(padded_lens)):
            d_i = np.int32(padded_offsets[i]) - real_off[i]
            mapped = jnp.where(out_idx >= np.int32(padded_offsets[i]),
                               out_idx - d_i, mapped)
        mapped = jnp.where(out_idx >= 0, mapped, -1)
        if want_padded:
            # the padded-concat index addresses each run's padded device
            # arrays directly — what the per-run value gather consumes
            return mapped, out_idx, count
        return mapped, count

    return fn


@functools.lru_cache(maxsize=256)
def _compiled_pipeline_cached(padded_lens: tuple, run_ws: tuple, w: int):
    """Jitted single-merge pipeline over cached device runs (see
    _make_cached_fn for the full contract)."""
    return DeviceKernel(_make_cached_fn(padded_lens, run_ws, w),
                        "merge_cached")


@functools.lru_cache(maxsize=256)
def _compiled_pipeline_cached_padded(padded_lens: tuple, run_ws: tuple,
                                     w: int):
    """As _compiled_pipeline_cached but also returning the padded-concat
    survivor index (value-residency materialization needs it)."""
    return DeviceKernel(_make_cached_fn(padded_lens, run_ws, w,
                                        want_padded=True),
                        "merge_cached_padded")


@functools.lru_cache(maxsize=64)
def _compiled_cached_val_gather(padded_lens: tuple, vl0: int, bucket: int):
    """Per-run masked value-row gather by PADDED-concat survivor index:
    run i owns indices [offs[i], offs[i]+padded_lens[i]). K clipped
    gathers + masked select — all HBM-bound, trivial next to the download."""
    import jax.numpy as jnp

    offs = np.cumsum([0] + list(padded_lens))

    def fn(val2ds, idx):
        out = jnp.zeros((bucket, vl0), jnp.uint8)
        for i, v in enumerate(val2ds):
            local = idx - np.int32(offs[i])
            ok = (local >= 0) & (local < np.int32(padded_lens[i]))
            rows = jnp.take(v, jnp.clip(local, 0, np.int32(padded_lens[i] - 1)),
                            axis=0)
            out = jnp.where(ok[:, None], rows, out)
        return out

    return DeviceKernel(fn, "val_gather_cached")


def materialize_cached_survivors(runs, device_runs, mapped_idx,
                                 padded_idx, count: int, kl0: int,
                                 vl0: int) -> KVBlock:
    """Cached-run analogue of materialize_device_survivors: value rows are
    gathered per-run on device by padded-concat index and downloaded as one
    block, overlapped with the host keys+aux gather by real-concat index.
    Preconditions (caller-checked): every run has val2d with one shared
    vl0, and the runs share the uniform layout (kl0, vl0)."""
    if count == 0:
        return KVBlock.empty()
    padded_lens = tuple(r.padded_len for r in device_runs)
    bucket = min(_pow2ceil(count, 1 << 16), int(padded_idx.shape[0]))
    fn = _compiled_cached_val_gather(padded_lens, vl0, bucket)
    out_dev = fn(tuple(r.val2d for r in device_runs), padded_idx[:bucket])
    return _finish_overlapped(runs, out_dev, mapped_idx, count, kl0, vl0)


_BACKENDS = {"cpu": CpuBackend(), "tpu": TpuBackend(), "jax": TpuBackend()}


def get_backend(name: str):
    return _BACKENDS[name]


def _concat(runs) -> KVBlock:
    """The runs as ONE block for a survivor gather that cannot index them
    where they lie (gather_runs' fallback): a copy of every arena when
    there is more than one run."""
    if len(runs) == 1:
        return runs[0]
    with _TRACE.span("concat", records=sum(b.n for b in runs)):
        return KVBlock.concat(runs)


def compact_blocks(blocks, opts: CompactOptions,
                   device_runs=None) -> CompactResult:
    """Merge K runs (newest first) into one sorted, deduped, filtered block.

    blocks[0] is the newest run (e.g. the freshest L0 file), blocks[-1] the
    oldest — matching LSM level semantics where a version in a newer run
    shadows the same key in an older one.

    device_runs: optional parallel list of cached DeviceRuns (entries may
    be None). When the backend is tpu and EVERY non-empty run has one, the
    merge consumes HBM-resident columns directly — no host packing, no
    re-upload (the engine's device-resident run cache, VERDICT-r2 item 4).
    """
    with _TRACE.span("compact",
                     records=sum(b.n for b in blocks)) as sp:
        result = _compact_blocks_impl(blocks, opts, device_runs)
        sp["records"] = result.stats.get("input_records", sp["records"])
        return result


def _compact_blocks_impl(blocks, opts: CompactOptions,
                         device_runs=None) -> CompactResult:
    if device_runs is not None:
        device_runs = [d for b, d in zip(blocks, device_runs) if b.n]
    runs = [b for b in blocks if b.n]
    if not runs:
        return CompactResult(KVBlock.empty(), _stats(0, 0))
    # bigger-than-device merges: split the key space into disjoint ranges
    # and compact each independently — dedup and every filter are per-key,
    # so range outputs concatenate into exactly the whole-merge result
    # (byte-equal; test-enforced). The reference handles the analogous
    # "input exceeds memory" case by iterating RocksDB's merge cursor;
    # a device kernel needs resident inputs, so capacity comes from
    # range decomposition instead.
    # (sorted runs only: the range cuts binary-search each run, so an
    # unsorted input — bulk-load ingest sets — must take the normal path,
    # whose pack step sorts runs locally before any device work)
    total_in = sum(b.n for b in runs)
    if (opts.backend != "cpu" and opts.runs_sorted
            and total_in > opts.max_device_records):
        return _compact_blockwise(runs, opts, total_in)
    # run priority travels in 8 bits of the packed (klen<<8 | prio) sort
    # column; wider merges pre-combine the newest runs (no filtering — only
    # the final merge may drop tombstones/expired) to stay within it
    while len(runs) > 255:
        head = compact_blocks(runs[:200], CompactOptions(
            now=opts.now, prefix_u32=opts.prefix_u32, backend=opts.backend,
            filter=False, runs_sorted=opts.runs_sorted))
        runs = [head.block] + runs[200:]
        device_runs = None
    backend = get_backend(opts.backend)
    now = opts.resolved_now()
    fargs = (now, opts.pidx, opts.partition_mask,
             bool(opts.bottommost), bool(opts.filter))

    def _cpu_lane() -> KVBlock:
        packed = pack_runs(runs, opts, need_sbytes=True)
        survivors = get_backend("cpu").survivors(packed, *fargs)
        return gather_runs(runs, survivors, len(survivors))

    def _device_lane() -> KVBlock:
        if (device_runs is not None and len(device_runs) == len(runs)
                and all(d is not None for d in device_runs)):
            # cheap checks first: uniform_layout() is four O(n) reductions,
            # wasted work whenever value residency is off (the default)
            vl0s = {d.vl0 for d in device_runs} \
                if all(d.val2d is not None for d in device_runs) else set()
            uni = _shared_uniform_layout(runs) if len(vl0s) == 1 else None
            if uni is not None and uni[1] == next(iter(vl0s)):
                # value residency: output values materialize on device
                mapped, padded, count = backend.survivors_cached_device(
                    device_runs, *fargs, want_padded=True)
                return materialize_cached_survivors(runs, device_runs,
                                                    mapped, padded, count,
                                                    *uni)
            dev_idx, count = backend.survivors_cached_device(device_runs,
                                                             *fargs)
            return gather_runs(runs, dev_idx, count)
        packed = pack_runs(runs, opts, need_sbytes=False)
        dev_idx, count = backend.survivors_device(packed, *fargs)
        return gather_runs(runs, dev_idx, count)

    if backend.name == "tpu":
        # the lane guard owns every device failure mode: deadline-abandoned
        # wedges, bounded retry on transient errors, byte-identical cpu
        # fallback, and the breaker that routes around a dead device
        from ..runtime.lane_guard import LANE_GUARD

        out = LANE_GUARD.run(_device_lane, _cpu_lane, op="compact")
    else:
        out = _cpu_lane()
    out = apply_post_filters(out, opts, now)
    # stats count RAW input rows (pre any pack-time intra-run dedup) so
    # every path — cpu, device, cached, sharded, blockwise — reports the
    # same input_records for the same inputs
    return CompactResult(out, _stats(sum(b.n for b in runs), out.n))


def apply_post_filters(out: KVBlock, opts: CompactOptions,
                       now: int) -> KVBlock:
    """Host-side post passes shared by every merge entry point (single,
    blockwise, batched): user-specified compaction rules run before the
    TTL rewrite, like KeyWithTTLCompactionFilter runs user ops first
    (:36-105), then the table default_ttl rewrite."""
    if opts.filter and opts.user_ops:
        from ..engine.compaction_rules import apply_operations

        drop, _ = apply_operations(out, opts.user_ops, now)
        if drop.any():
            out = out.gather(np.nonzero(~drop)[0])
    if opts.filter and opts.default_ttl > 0:
        _apply_default_ttl(out, now + opts.default_ttl)
    return out


def _slice_block(b: KVBlock, lo: int, hi: int) -> KVBlock:
    """Zero-copy row slice: arenas shared, columns sliced (offsets remain
    valid into the full arena; gather compacts later)."""
    return KVBlock(b.key_arena, b.key_off[lo:hi], b.key_len[lo:hi],
                   b.val_arena, b.val_off[lo:hi], b.val_len[lo:hi],
                   b.expire_ts[lo:hi], b.hash32[lo:hi], b.deleted[lo:hi])


def _compact_blockwise(runs, opts: CompactOptions,
                       total_in: int) -> CompactResult:
    """Range-decomposed compaction for merges too big for device memory:
    boundary keys from the largest run's quantiles cut EVERY run into
    aligned disjoint key ranges; each range merges/dedups/filters
    independently on the device and outputs concatenate in key order.

    With PEGASUS_COMPACT_PIPELINE_DEPTH > 1 (default 2) the ranges run
    double-buffered (ops/pipeline.py): range i+1 packs/uploads on a host
    worker and range i-1 gathers/post-filters while range i runs its
    device merge — the stages pay max() instead of sum()."""
    from .pipeline import pipeline_depth

    n_ranges = max(2, -(-total_in // opts.max_device_records))
    pivot = max(runs, key=lambda b: b.n)
    boundaries = []
    for j in range(1, n_ranges):
        k = pivot.key(min(pivot.n - 1, j * pivot.n // n_ranges))
        if not boundaries or k > boundaries[-1]:
            boundaries.append(k)
    cuts = [[0] * len(runs)]
    for k in boundaries:
        cuts.append([b.lower_bound(k) for b in runs])
    cuts.append([b.n for b in runs])
    # long keys trigger pack_runs' suffix-rank path, which CONCATENATES its
    # inputs — zero-copy slices would drag the full shared arenas into
    # every range (n_ranges x total memory, on exactly the bounded-memory
    # path). Compact such slices down to their own rows first.
    long_keys = max(int(b.key_len.max()) for b in runs) > 4 * opts.prefix_u32
    jobs = []  # (non-empty range_runs, range_total, direct)
    for lo_cut, hi_cut in zip(cuts, cuts[1:]):
        range_runs = [_slice_block(b, lo, hi)
                      for b, lo, hi in zip(runs, lo_cut, hi_cut)]
        if long_keys:
            range_runs = [rb.gather(np.arange(rb.n, dtype=np.int64))
                          for rb in range_runs]
        range_runs = [rb for rb in range_runs if rb.n]
        range_total = sum(rb.n for rb in range_runs)
        if range_total == 0:
            continue
        # direct ranges re-enter compact_blocks whole (with its own lane
        # guard) instead of the split pack/device/gather stages: degenerate
        # non-shrinking ranges, ranges still over budget (skewed keys ->
        # recursive blockwise), and >255-run merges (pre-combine path)
        direct = (range_total >= total_in
                  or range_total > opts.max_device_records
                  or len(range_runs) > 255)
        jobs.append((range_runs, range_total, direct))
    if len(jobs) > 1 and pipeline_depth() > 1:
        return _compact_blockwise_pipelined(jobs, opts, total_in)
    out_blocks = []
    n_out = 0
    for range_runs, range_total, _ in jobs:
        res = compact_blocks(range_runs,
                             _range_opts(opts, range_total, total_in))
        if res.block.n:
            out_blocks.append(res.block)
            n_out += res.block.n
    out = (KVBlock.concat(out_blocks) if len(out_blocks) != 1
           else out_blocks[0])
    return CompactResult(out, _stats(total_in, n_out))


def _range_opts(opts: CompactOptions, range_total: int,
                total_in: int) -> CompactOptions:
    """Per-range CompactOptions: a degenerate key distribution (e.g. one
    repeated key) cannot shrink its range — merge it directly with a
    raised budget rather than recurse forever."""
    if range_total >= total_in:
        from dataclasses import replace

        return replace(opts, max_device_records=range_total + 1)
    return opts


def _compact_blockwise_pipelined(jobs, opts: CompactOptions,
                                 total_in: int) -> CompactResult:
    """Double-buffered range loop. The WHOLE pipelined run executes under
    one lane guard: the device stages run in the guard's worker thread
    (so a wedge anywhere — including a wedged prefetch the caller is
    stalled on — is deadline-abandoned with stage attribution), and the
    fallback drains the pipeline's in-flight workers before rerunning
    every range serially on the cpu backend, byte-identical by the
    backend contract."""
    from dataclasses import replace

    from .pipeline import CompactPipeline

    # pin `now` once: the device attempt and a cpu rerun must filter
    # against the same clock or a fallback could drop a different TTL set
    now = opts.resolved_now()
    opts = replace(opts, now=now)
    fargs = (now, opts.pidx, opts.partition_mask,
             bool(opts.bottommost), bool(opts.filter))
    backend = get_backend(opts.backend)

    def _device_pipelined() -> list:
        pipe = CompactPipeline()

        def _prefetch(job):
            range_runs, _, direct = job
            if direct:
                return None
            packed = pack_runs(range_runs, opts, need_sbytes=False)
            return backend.prepare(packed)  # h2d upload on the worker

        def _dispatch(i, prep):
            range_runs, range_total, direct = jobs[i]
            if direct:
                return compact_blocks(
                    range_runs, _range_opts(opts, range_total, total_in)
                ).block
            return backend.survivors_device(prep, *fargs)

        def _finish(i, disp):
            range_runs, _, direct = jobs[i]
            if direct:
                return disp
            dev_idx, count = disp
            concat = (range_runs[0] if len(range_runs) == 1
                      else KVBlock.concat(range_runs))
            out = gather_runs([concat], dev_idx, count)
            return apply_post_filters(out, opts, now)

        return pipe.map(jobs, _prefetch, _dispatch, _finish)

    def _cpu_serial() -> list:
        return [
            compact_blocks(
                range_runs,
                replace(_range_opts(opts, range_total, total_in),
                        backend="cpu")).block
            for range_runs, range_total, _ in jobs]

    if backend.name == "tpu":
        from ..runtime.lane_guard import LANE_GUARD

        # the guard covers the WHOLE pipelined run, so its deadline must
        # scale with the number of ranges — a large healthy compaction's
        # legitimate device time is ~per-range time x n, and a fixed
        # per-range deadline would falsely abandon it (and walk the
        # breaker open). A wedge still aborts within n x deadline.
        # eff <= 0 = deadline disabled, preserved by the multiply.
        eff = LANE_GUARD.effective_deadline_s()
        scaled = eff * len(jobs) if eff and eff > 0 else eff
        blocks = LANE_GUARD.run(_device_pipelined, _cpu_serial,
                                op="compact", deadline_s=scaled)
    else:
        blocks = _device_pipelined()
    out_blocks = [b for b in blocks if b.n]
    n_out = sum(b.n for b in out_blocks)
    out = (KVBlock.concat(out_blocks) if len(out_blocks) != 1
           else out_blocks[0])
    return CompactResult(out, _stats(total_in, n_out))


def sort_block(block: KVBlock, opts: CompactOptions = None) -> KVBlock:
    """Flush path: sort one run by key, newest-wins dedup, no filtering
    (RocksDB flush writes every live memtable record; the reference's TTL
    filter only runs at compaction)."""
    opts = opts or CompactOptions()
    flush_opts = CompactOptions(
        now=opts.now, prefix_u32=opts.prefix_u32, backend=opts.backend,
        filter=False, runs_sorted=False,
    )
    return compact_blocks([block], flush_opts).block


def merge_body(cols, rank, klen, prio, expire, deleted, hash32, valid,
               now, pidx, pmask, bottommost, do_filter, pos=None):
    """Single-array device merge: full sort + dedup + filter on jnp arrays.

    Used by the shard_map'd multi-chip path (parallel.sharded_compact),
    whose all_to_all routing scrambles run order, and by the driver's
    single-chip compile check. Returns (perm, keep) in sorted order.
    Input length must be a power of two (callers pad).

    `pos` (uint32) is the LAST sort key: the tie-break among rows with
    identical (key, prio) — i.e. duplicate keys within one run. Sort
    networks are not stable, so without a keyed position the surviving
    version of an intra-run duplicate is nondeterministic (and the
    sharded path's all_to_all re-orders rows, so its local iota is NOT
    original order). Callers with scrambled layouts pass the original
    concat index; None = rows are in original order, use iota.
    """
    import jax.numpy as jnp
    from jax import lax

    from .device_sort import sort_network

    n = rank.shape[0]
    big = jnp.uint32(0xFFFFFFFF)
    key_cols = [jnp.where(valid, c, big) for c in cols]
    key_cols.append(jnp.where(valid, rank, big))
    key_cols.append(jnp.where(valid, klen, big))
    iota = lax.iota(jnp.int32, n)
    if pos is None:
        pos = iota.astype(jnp.uint32)
    sort_ops = key_cols + [jnp.where(valid, prio, big),
                           jnp.where(valid, pos, big)]
    out = sort_network(sort_ops + [iota], nk=len(sort_ops))
    s_key_cols = out[: len(key_cols)]
    perm = out[-1]
    same_tail = functools.reduce(
        jnp.logical_and, [c[1:] == c[:-1] for c in s_key_cols]
    )
    same = jnp.concatenate([jnp.zeros(1, dtype=bool), same_tail])
    keep = valid[perm] & ~same
    s_expire = expire[perm]
    s_deleted = deleted[perm]
    s_hash = hash32[perm]
    expired = (s_expire > 0) & (s_expire <= now)
    stale = jnp.where(pmask > 0, (s_hash & pmask) != pidx, False)
    tomb = s_deleted & bottommost
    keep_f = keep & ~expired & ~stale & ~tomb
    keep = jnp.where(do_filter, keep_f, keep)
    return perm, keep


def _apply_default_ttl(block: KVBlock, new_expire: int) -> None:
    """Rewrite expire_ts=0 records to the table default TTL, in place.

    Mirrors KeyWithTTLCompactionFilter's value rewrite when a table-level
    default_ttl app-env is set (src/server/key_ttl_compaction_filter.h:56-76).
    expire_ts sits at value offset 0 (v0/v1) or 1 (self-describing v2).
    """
    targets = np.nonzero((block.expire_ts == 0) & ~block.deleted)[0]
    if len(targets) == 0:
        return
    off = block.val_off[targets]
    vlen = block.val_len[targets]
    has_hdr = vlen > 0
    first = np.where(has_hdr, block.val_arena[np.minimum(off, len(block.val_arena) - 1)], 0)
    hdr = (first & 0x80) != 0
    # the 4-byte BE field must fit inside THIS record's value bytes: a
    # value shorter than its own expire_ts field (truncated ingest, raw
    # test fixtures) is skipped outright — rewriting it would scribble
    # into the neighboring record's arena bytes (or off the arena end)
    fits = vlen >= np.where(hdr, 5, 4)
    if not bool(fits.all()):
        targets, off, hdr = targets[fits], off[fits], hdr[fits]
        if len(targets) == 0:
            return
    off = off + np.where(hdr, 1, 0)
    be = np.array(
        [(new_expire >> 24) & 0xFF, (new_expire >> 16) & 0xFF,
         (new_expire >> 8) & 0xFF, new_expire & 0xFF],
        dtype=np.uint8,
    )
    for j in range(4):
        block.val_arena[off + j] = be[j]
    block.expire_ts[targets] = np.uint32(new_expire)


def _stats(n_in: int, n_out: int) -> dict:
    return {"input_records": n_in, "output_records": n_out, "dropped": n_in - n_out}
