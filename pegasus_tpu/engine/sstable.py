"""SST ("sorted string table") file format — columnar, device-loadable.

Unlike RocksDB's row-oriented block format, an SST here is a serialized
KVBlock: byte arenas + fixed-width columns, so a compaction input loads with
a handful of large reads straight into numpy arrays and the fixed-width
columns stream to HBM with zero per-record host work. Layout:

    magic "PGTS1\\n" | u32 header_len | header json | sections (raw bytes)

The header carries section offsets/dtypes/shapes plus engine metadata
(min/max key, record count, level, data_version, smallest decree info).
"""

import json
import os
import struct

import numpy as np

from .block import KVBlock
from ..runtime.perf_counters import counters

MAGIC = b"PGTS1\n"

# zero-copy mmap loads (ISSUE 20): flatlines when PEGASUS_NATIVE=0
_C_SST_MMAP = counters.rate("native.sst_mmap_count")


class CorruptionError(ValueError):
    """Typed on-disk corruption: bad magic, truncated file, unparseable
    header, or a section whose crc32 no longer matches what write_sst
    recorded. Subclasses ValueError so pre-existing broad handlers (e.g.
    manifest orphan adoption) keep treating a rotten file as unusable
    rather than crashing, while new code can catch corruption by type.
    Raised by read_header/read_sst/verify_sst — never a raw struct.error
    or JSONDecodeError."""

    def __init__(self, path: str, detail: str):
        super().__init__(f"{path}: {detail}")
        self.path = path
        self.detail = detail


_COLUMNS = [
    ("key_arena", np.uint8),
    ("key_off", np.int64),
    ("key_len", np.int32),
    ("val_arena", np.uint8),
    ("val_off", np.int64),
    ("val_len", np.int32),
    ("expire_ts", np.uint32),
    ("hash32", np.uint32),
    ("deleted", np.bool_),
]


_BLOOM_SALTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)


def _bloom_build(hash32: np.ndarray) -> tuple:
    """Bloom filter over the per-record hashkey hash (the reference's
    hashkey prefix bloom, src/server/hashkey_transform.h:31-60: one probe
    set per hash_key, shared by all its sort_keys). ~10 bits/distinct-hash,
    k=5; returns (bits bytes, log2_m)."""
    uniq = np.unique(hash32)
    m = 64
    while m < len(uniq) * 10:
        m <<= 1
    log2m = m.bit_length() - 1
    bits = np.zeros(m // 8, dtype=np.uint8)
    h = uniq.astype(np.uint64)
    for salt in _BLOOM_SALTS:
        pos = ((h * np.uint64(salt)) & np.uint64(0xFFFFFFFF)) >> np.uint64(32 - log2m)
        np.bitwise_or.at(bits, (pos >> np.uint64(3)).astype(np.int64),
                         (np.uint8(1) << (pos & np.uint64(7)).astype(np.uint8)))
    return bits.tobytes(), log2m


def write_sst(path: str, block: KVBlock, meta: dict = None,
              compression: str = "none", bloom: tuple = None) -> dict:
    """Write atomically (tmp+rename). Returns the header dict.

    compression="zlib" deflates each section (the per-table rocksdb
    compression knob, reference value-compression options); readers
    auto-detect from the header, so tables can mix files.
    bloom=(hex, log2m) reuses a precomputed bloom for this exact block
    (deferred installs already built one in SSTable.from_block — the
    multi-hash O(n) pass must not run twice per file)."""
    import time as _time

    from ..runtime.fail_points import inject
    from ..runtime.perf_counters import counters
    from ..runtime.tracing import COMPACT_TRACER

    t0 = _time.perf_counter()
    nbytes = block.key_bytes_total + block.val_bytes_total
    with COMPACT_TRACER.span("sst_write", records=block.n, nbytes=nbytes):
        inject("engine.sst_write")
        header = _write_sst_impl(path, block, meta, compression, bloom)
    counters.rate("engine.sst_write_count").increment()
    counters.rate("engine.sst_write_bytes").increment(nbytes)
    counters.percentile("engine.sst_write_s").set(
        round(_time.perf_counter() - t0, 6))
    return header


def _write_sst_impl(path: str, block: KVBlock, meta: dict,
                    compression: str, bloom: tuple = None) -> dict:
    import zlib

    sections = {}
    payload = []
    offset = 0
    for name, dtype in _COLUMNS:
        arr = np.ascontiguousarray(getattr(block, name), dtype=dtype)
        # the column's own bytes (writable or an mmap's read-only pages),
        # crc'd and written where they lie: no tobytes() copy
        raw = arr.reshape(-1).view(np.uint8)
        stored = zlib.compress(raw, 1) if compression == "zlib" else raw
        sections[name] = {"offset": offset, "nbytes": len(stored),
                          "raw_nbytes": len(raw),
                          "dtype": np.dtype(dtype).str,
                          "shape": list(arr.shape),
                          "compression": compression,
                          "crc32": zlib.crc32(stored) & 0xFFFFFFFF}
        payload.append(stored)
        offset += len(stored)
    if bloom is not None:
        bloom_hex, bloom_log2m = bloom
    else:
        bloom_hex, bloom_log2m = "", 0
        if block.n:
            bloom_bits, bloom_log2m = _bloom_build(block.hash32)
            bloom_hex = bloom_bits.hex()
    header = {
        "sections": sections,
        "meta": dict(meta or {}),
        "n": block.n,
        "min_key": block.key(0).hex() if block.n else None,
        "max_key": block.key(block.n - 1).hex() if block.n else None,
        "data_bytes": block.key_bytes_total + block.val_bytes_total,
        "bloom": bloom_hex,
        "bloom_log2m": bloom_log2m,
    }
    hdr = json.dumps(header).encode()
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<I", len(hdr)))
        f.write(hdr)
        for raw in payload:
            f.write(raw)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return header


def _read_header_open(f, path: str) -> dict:
    """Header parse over an open file; every failure mode is typed."""
    magic = f.read(len(MAGIC))
    if magic != MAGIC:
        raise CorruptionError(path, f"bad SST magic {magic!r}")
    raw_len = f.read(4)
    if len(raw_len) < 4:
        raise CorruptionError(path, "truncated before header length")
    (hlen,) = struct.unpack("<I", raw_len)
    raw_hdr = f.read(hlen)
    if len(raw_hdr) < hlen:
        raise CorruptionError(
            path, f"truncated header ({len(raw_hdr)}/{hlen} bytes)")
    try:
        return json.loads(raw_hdr)
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CorruptionError(path, f"unparseable header: {e}") from e


def read_header(path: str) -> dict:
    with open(path, "rb") as f:
        return _read_header_open(f, path)


def _read_section(f, path: str, base: int, name: str, sec: dict) -> bytes:
    """One stored section, crc-checked when the header carries a crc32
    (legacy pre-checksum headers don't — they stay readable unchecked)."""
    import zlib

    f.seek(base + sec["offset"])
    stored = f.read(sec["nbytes"])
    if len(stored) < sec["nbytes"]:
        raise CorruptionError(
            path, f"section {name} truncated "
                  f"({len(stored)}/{sec['nbytes']} bytes)")
    want = sec.get("crc32")
    if want is not None and (zlib.crc32(stored) & 0xFFFFFFFF) != want:
        raise CorruptionError(
            path, f"section {name} crc32 mismatch "
                  f"(stored {want:#010x}, "
                  f"computed {zlib.crc32(stored) & 0xFFFFFFFF:#010x})")
    if sec.get("compression", "none") == "zlib":
        try:
            stored = zlib.decompress(stored)
        except zlib.error as e:
            raise CorruptionError(
                path, f"section {name} undecompressable: {e}") from e
    return stored


def read_sst(path: str) -> tuple:
    """-> (KVBlock, header dict). With PEGASUS_NATIVE on (the default)
    uncompressed sections are ZERO-COPY views over an mmap of the file
    (ISSUE 20); with the knob off, the classic read()+copy path."""
    from .. import native

    if native.native_on():
        return _read_sst_mmap(path)
    with open(path, "rb") as f:
        header = _read_header_open(f, path)
        base = f.tell()
        cols = {}
        for name, _ in _COLUMNS:
            try:
                sec = header["sections"][name]
            except (KeyError, TypeError) as e:
                raise CorruptionError(
                    path, f"header missing section {name}") from e
            raw = _read_section(f, path, base, name, sec)
            try:
                cols[name] = np.frombuffer(
                    raw, dtype=np.dtype(sec["dtype"])
                ).reshape(sec["shape"]).copy()
            except (ValueError, TypeError) as e:
                raise CorruptionError(
                    path, f"section {name} unmaterializable: {e}") from e
    return KVBlock(**cols), header


def _read_sst_mmap(path: str) -> tuple:
    """read_sst's zero-copy twin: ONE mmap of the whole file, each
    uncompressed section materialized as an np.frombuffer view over the
    mapping — no f.read() double copy, and page-cache pages are shared
    across processes opening the same SST.

    Lifetime: every view's .base chain pins the memoryview, which pins
    the mmap object, which holds the kernel mapping open — and a mapped
    inode's data stays valid after the path is UNLINKED (compaction
    removes its inputs while readers may still hold their blocks). So a
    block loaded here stays readable for exactly as long as any of its
    arrays is referenced, file deletion notwithstanding — the lifetime
    regression test in test_native_dataplane.py pins this. The views are
    read-only (ACCESS_READ), which is safe because SST-loaded blocks are
    never mutated in place: compaction's in-place rewrites
    (_rewrite_expire / _apply_default_ttl) only touch freshly gathered
    output blocks. zlib-compressed sections decompress into fresh bytes
    as before (nothing to alias).
    """
    import mmap
    import zlib

    with open(path, "rb") as f:
        header = _read_header_open(f, path)
        base = f.tell()
        try:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except (ValueError, OSError) as e:  # empty / unmappable file
            raise CorruptionError(path, f"unmappable: {e}") from e
    _C_SST_MMAP.increment()
    mv = memoryview(mm)
    cols = {}
    for name, _ in _COLUMNS:
        try:
            sec = header["sections"][name]
        except (KeyError, TypeError) as e:
            raise CorruptionError(
                path, f"header missing section {name}") from e
        off, n = base + sec["offset"], sec["nbytes"]
        if off < base or n < 0 or off + n > len(mm):
            raise CorruptionError(
                path, f"section {name} truncated "
                      f"({max(0, len(mm) - off)}/{n} bytes)")
        stored = mv[off:off + n]
        want = sec.get("crc32")
        if want is not None and (zlib.crc32(stored) & 0xFFFFFFFF) != want:
            raise CorruptionError(
                path, f"section {name} crc32 mismatch "
                      f"(stored {want:#010x}, "
                      f"computed {zlib.crc32(stored) & 0xFFFFFFFF:#010x})")
        if sec.get("compression", "none") == "zlib":
            try:
                stored = zlib.decompress(stored)
            except zlib.error as e:
                raise CorruptionError(
                    path, f"section {name} undecompressable: {e}") from e
        try:
            cols[name] = np.frombuffer(
                stored, dtype=np.dtype(sec["dtype"])).reshape(sec["shape"])
        except (ValueError, TypeError) as e:
            raise CorruptionError(
                path, f"section {name} unmaterializable: {e}") from e
    return KVBlock(**cols), header


def verify_sst(path: str) -> int:
    """Full-file integrity pass (scrub + fsck): magic, header parse, and
    every section's length + crc32 — without materializing a KVBlock.
    Returns the byte count read; raises CorruptionError on any finding."""
    with open(path, "rb") as f:
        header = _read_header_open(f, path)
        base = f.tell()
        scanned = base
        sections = header.get("sections")
        if not isinstance(sections, dict):
            raise CorruptionError(path, "header missing sections")
        for name, _ in _COLUMNS:
            sec = sections.get(name)
            if not isinstance(sec, dict):
                raise CorruptionError(path, f"header missing section {name}")
            scanned += len(_read_section(f, path, base, name, sec))
    return scanned


class SSTable:
    """An open SST: header always resident, block lazily loaded.

    Point lookups binary-search the key arena; min/max keys let the level
    structure skip files without touching their data.
    """

    def __init__(self, path: str):
        self.path = path
        self.header = read_header(path)
        self._init_runtime_state()

    def _init_runtime_state(self):
        self._block = None
        self._device_run = None
        self._device_uncacheable = False
        self._values_uncacheable = False
        # deferred write-out (engine pipelined installs): False while the
        # file has not landed on disk yet — the manifest writer must not
        # reference it until it has
        self._on_disk = True
        # set when the engine released this file's device columns for
        # good (inputs consumed by a merge): a late async residency prime
        # must not re-pin HBM for a dead file
        self._device_retired = False
        # engine-side prime coordination: _prime_inflight keeps an async
        # prime and an inline caller from double-uploading one file;
        # _device_budgeted records whether _device_run's bytes were added
        # to the engine's HBM budget (a release only subtracts then)
        self._prime_inflight = False
        self._device_budgeted = False
        self._bloom = None
        if self.header.get("bloom"):
            self._bloom = np.frombuffer(
                bytes.fromhex(self.header["bloom"]), dtype=np.uint8)
        self._bloom_log2m = int(self.header.get("bloom_log2m", 0))

    @classmethod
    def from_block(cls, path: str, block: KVBlock,
                   meta: dict = None) -> "SSTable":
        """In-memory SSTable over a not-yet-written block, for the
        engine's deferred (pipelined) installs: the header is synthesized
        from the block so reads/blooms/level bookkeeping work immediately,
        while write_sst lands the file on a pool worker. _on_disk stays
        False until it does; `sections` is empty because the cached block
        makes the disk read path unreachable (and the real header is
        written by write_sst)."""
        self = cls.__new__(cls)
        self.path = path
        bloom_hex, bloom_log2m = "", 0
        if block.n:
            bloom_bits, bloom_log2m = _bloom_build(block.hash32)
            bloom_hex = bloom_bits.hex()
        self.header = {
            "sections": {},
            "meta": dict(meta or {}),
            "n": block.n,
            "min_key": block.key(0).hex() if block.n else None,
            "max_key": block.key(block.n - 1).hex() if block.n else None,
            "data_bytes": block.key_bytes_total + block.val_bytes_total,
            "bloom": bloom_hex,
            "bloom_log2m": bloom_log2m,
        }
        self._init_runtime_state()
        self._block = block
        self._on_disk = False
        return self

    @property
    def n(self) -> int:
        return self.header["n"]

    @property
    def data_bytes(self) -> int:
        db = self.header.get("data_bytes")
        if db is None:  # pre-data_bytes header: derive from the sections
            db = (self.header["sections"]["key_arena"]["nbytes"]
                  + self.header["sections"]["val_arena"]["nbytes"])
        return int(db)

    def maybe_contains_hash(self, h32) -> bool:
        """Hashkey bloom probe; False = definitely absent (no disk read)."""
        if self._bloom is None:
            return self.n > 0
        h = np.uint64(h32)
        for salt in _BLOOM_SALTS:
            pos = ((h * np.uint64(salt)) & np.uint64(0xFFFFFFFF)) \
                >> np.uint64(32 - self._bloom_log2m)
            if not (self._bloom[int(pos >> np.uint64(3))]
                    >> np.uint8(pos & np.uint64(7))) & 1:
                return False
        return True

    @property
    def min_key(self):
        mk = self.header["min_key"]
        return bytes.fromhex(mk) if mk else None

    @property
    def max_key(self):
        mk = self.header["max_key"]
        return bytes.fromhex(mk) if mk else None

    @property
    def meta(self) -> dict:
        return self.header["meta"]

    def block(self) -> KVBlock:
        if self._block is None:
            from ..runtime.perf_counters import counters
            from ..runtime.tracing import COMPACT_TRACER

            counters.rate("engine.sst_block_load").increment()
            # the one place a file's data is loaded: read (or mmap) + crc
            # of every section — a merge's inputs, a read's first touch
            with COMPACT_TRACER.span("sst_read", records=self.n,
                                     nbytes=self.data_bytes):
                self._block, _ = read_sst(self.path)
        return self._block

    def maybe_contains(self, key: bytes) -> bool:
        return self.n > 0 and self.min_key <= key <= self.max_key

    def find(self, key: bytes) -> int:
        """Index of `key` or -1; binary search over the sorted key column."""
        if not self.maybe_contains(key):
            return -1
        b = self.block()
        lo, hi = 0, b.n - 1
        while lo <= hi:
            mid = (lo + hi) // 2
            k = b.key(mid)
            if k < key:
                lo = mid + 1
            elif k > key:
                hi = mid - 1
            else:
                return mid
        return -1

    def lower_bound(self, key: bytes) -> int:
        """First index with block.key(i) >= key (n if none)."""
        return self.block().lower_bound(key)

    @property
    def device_index(self):
        """The HBM-resident read index for this file, or None when the
        file is not device-servable: the DeviceRun primed at flush/
        compaction time, carrying the fence-pointer index its prime built
        as a byproduct (ops/device_lookup.py). The engine's batched read
        path (db.get_batch) probes this instead of the host binary
        search; a retired run (consumed by a merge) stops serving."""
        dr = self._device_run
        if dr is None or self._device_retired or \
                getattr(dr, "fence", None) is None:
            return None
        return dr

    def device_run(self, prefix_u32: int, with_values: bool = False):
        """Lazily pack + upload this file's sort columns to the device and
        PIN them for its lifetime (the engine's HBM-resident run cache,
        SURVEY §5.7c): compactions this file joins read HBM instead of
        re-packing and re-crossing PCIe every time. Returns None when the
        run is uncacheable (keys beyond the prefix window need per-merge
        suffix ranks). with_values additionally pins uniform-layout value
        rows (value residency; see EngineOptions.device_values)."""
        needs_pack = self._device_run is None or (
            # upgrade a value-less cached run when values are now wanted
            # (e.g. primed earlier by a caller with the default flag) —
            # unless this file's values already proved unpackable
            # (non-uniform layout): retrying would re-upload the whole
            # run to HBM on every compaction it joins
            with_values and self._device_run.val2d is None
            and not self._values_uncacheable)
        if needs_pack and not self._device_uncacheable:
            from ..ops.compact import pack_run_device

            self._device_run = pack_run_device(self.block(), prefix_u32,
                                               with_values=with_values)
            if self._device_run is None:
                self._device_uncacheable = True
            elif with_values and self._device_run.val2d is None:
                self._values_uncacheable = True
        return self._device_run

    def release(self):
        self._block = None
        self._device_run = None
        self._device_uncacheable = False
        self._values_uncacheable = False
