"""Host-side packing: variable-length keys -> fixed-width device sort keys.

Device sorts need fixed-width keys. A stored key's first `4*W` bytes are
packed big-endian into W uint32 lanes, so unsigned u32 lexicographic order
over the lanes == byte order over the prefix (shorter keys zero-pad, and the
[u16 len] prefix of the key format guarantees a shorter hash_key never
zero-pad-collides with a longer one's real bytes except when one key is a
strict prefix of another — exactly the cases `compute_suffix_ranks` breaks).

The full device sort key is (prefix_lanes..., suffix_rank, key_len):

  - suffix_rank breaks ties between *long* keys (> window) sharing a prefix
    window: collision groups are found on host (rare — needs identical first
    4*W bytes), full keys compared within the group, and a dense rank
    assigned. Equal full keys share a rank, which the dedup kernel relies on.
  - key_len breaks the remaining ties exactly: two short keys with equal
    padded windows differ only in trailing 0x00 bytes (shorter is
    byte-smaller), and a short key whose window matches long keys is their
    strict byte prefix (sorts first; key_len < window bytes < long key_len).

So (window, rank, len) equality <=> full-key equality, and its order is full
byte order — no host comparisons outside collision groups.
"""

import json
import struct

import numpy as np

# The CAP on the prefix window, in u32 lanes (64 bytes). A run's own window
# is what its longest key needs, `window_lanes(max key length)`: 7 lanes for
# the 26-byte YCSB / bulk-fill keys, 13 for the geo index table's 51-byte
# ones. Up to the cap a run holds its FULL keys in its lanes (no suffix
# ranks, HBM-resident, device-read); only beyond it does the suffix-rank
# path below take over.
DEFAULT_PREFIX_U32 = 16


def window_lanes(max_key_len: int, cap_u32: int = DEFAULT_PREFIX_U32) -> int:
    """Lanes a run of keys up to `max_key_len` bytes packs into: enough
    for its longest key, at most the cap. The window follows the data, so
    runs of short keys keep their narrow programs whatever the cap is."""
    return max(1, min(-(-max_key_len // 4), cap_u32))

# ---------------------------------------------------------------- run wire
# The pack/serialize boundary for shipping whole runs between processes
# (ISSUE 14 compaction offload): a KVBlock flattened to one deterministic
# byte string — tiny json header (column dtypes/shapes) + the raw column
# buffers in declaration order. Distinct from the SST file format on
# purpose: no bloom, no engine meta, no fsync — this is a TRANSFER form
# whose md5 is a content-address, not a storage format.

_RUN_MAGIC = b"PGRN1\n"
_RUN_COLUMNS = (
    ("key_arena", np.uint8), ("key_off", np.int64), ("key_len", np.int32),
    ("val_arena", np.uint8), ("val_off", np.int64), ("val_len", np.int32),
    ("expire_ts", np.uint32), ("hash32", np.uint32), ("deleted", np.bool_),
)


def pack_run_bytes(block) -> bytes:
    """One KVBlock -> deterministic wire bytes (same block, same bytes —
    the offload resume/dedup key is the md5 of this)."""
    cols = {}
    parts = []
    for name, dtype in _RUN_COLUMNS:
        arr = np.ascontiguousarray(getattr(block, name), dtype=dtype)
        raw = arr.tobytes()
        cols[name] = {"dtype": np.dtype(dtype).str, "shape": list(arr.shape),
                      "nbytes": len(raw)}
        parts.append(raw)
    hdr = json.dumps({"n": int(block.n), "cols": cols},
                     sort_keys=True).encode()
    return b"".join([_RUN_MAGIC, struct.pack("<I", len(hdr)), hdr] + parts)


def unpack_run_bytes(data: bytes):
    """Wire bytes -> KVBlock (inverse of pack_run_bytes)."""
    from ..engine.block import KVBlock

    if data[:len(_RUN_MAGIC)] != _RUN_MAGIC:
        raise ValueError("bad run wire magic")
    (hlen,) = struct.unpack_from("<I", data, len(_RUN_MAGIC))
    base = len(_RUN_MAGIC) + 4
    hdr = json.loads(data[base:base + hlen])
    off = base + hlen
    kwargs = {}
    for name, _ in _RUN_COLUMNS:
        sec = hdr["cols"][name]
        raw = data[off:off + sec["nbytes"]]
        if len(raw) != sec["nbytes"]:
            raise ValueError(f"truncated run wire column {name}")
        kwargs[name] = np.frombuffer(raw, dtype=np.dtype(sec["dtype"])) \
            .reshape(sec["shape"]).copy()
        off += sec["nbytes"]
    return KVBlock(**kwargs)


def pack_sbytes(prefix_cols, klen, rank=None):
    """Fixed-width big-endian byte string per record: (prefix cols..,
    [rank,] klen) -> numpy 'S' array whose memcmp order equals the device
    sort order (prio excluded — callers order equal keys by run priority).

    numpy 'S' comparison strips trailing NULs then compares
    lexicographically, which for equal itemsize is memcmp-equivalent
    (first differing byte decides either way; all-equal iff identical).
    """
    cols = list(prefix_cols) + ([rank] if rank is not None else []) + [klen]
    n = len(klen)
    packed = np.zeros((n, len(cols)), dtype=">u4")
    for i, c in enumerate(cols):
        packed[:, i] = c
    return packed.view(f"S{4 * len(cols)}").ravel()


def pack_key_prefixes(key_arena, key_off, key_len, width_u32: int = DEFAULT_PREFIX_U32):
    """-> uint32[n, width_u32], big-endian packed, zero-padded."""
    from .. import native

    n = len(key_off)
    w_bytes = width_u32 * 4
    if n == 0:
        return np.zeros((0, width_u32), np.uint32)
    if native.available():
        return native.pack_prefixes(key_arena, key_off, key_len, width_u32)
    pos = np.arange(w_bytes, dtype=np.int64)
    idx = key_off[:, None] + pos[None, :]
    valid = pos[None, :] < key_len[:, None]
    b = np.where(valid, key_arena[np.minimum(idx, len(key_arena) - 1)], 0).astype(np.uint32)
    b = b.reshape(n, width_u32, 4)
    return (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]


def compute_suffix_ranks(block, width_u32: int = DEFAULT_PREFIX_U32, prefixes=None):
    """-> uint32[n]: dense order rank among records sharing a prefix window.

    0 for records with a unique prefix (the common case: the loop below only
    touches collision groups). Equal full keys map to the same rank.
    """
    n = block.n
    ranks = np.zeros(n, np.uint32)
    over = np.nonzero(block.key_len > width_u32 * 4)[0]
    if len(over) == 0:
        return ranks
    if prefixes is None:
        prefixes = pack_key_prefixes(block.key_arena, block.key_off, block.key_len, width_u32)
    # only long keys need ranks: short-key ties are resolved by the key_len
    # sort column (see module docstring)
    groups = {}
    for i in over:
        groups.setdefault(prefixes[i].tobytes(), []).append(int(i))
    for g in groups.values():
        if len(g) < 2:
            continue
        keyed = sorted((block.key(i), i) for i in g)
        rank = 0
        prev = None
        for k, i in keyed:
            if prev is not None and k != prev:
                rank += 1
            ranks[i] = rank
            prev = k
    return ranks
