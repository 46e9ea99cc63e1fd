"""Inputs made from --seed: the bulk-fill runs of the compaction cells and
the records, values and key choice of the served cells.

Copied from the repo's own generators so a later PR cannot move them:
`make_run` / `presort_run` from bench.py (the same columns and fractions,
the sort done on the plain key bytes with numpy instead of through
ops/packing.py), `record` / `sortkey` from chip_smoke.py (values made
self-describing so a reader can check any answer on its own), `ZipfKeys`
from bench.py (Gray et al.'s quick zipfian, scrambled as YCSB does).
Nothing here imports jax or the program; `to_kvblock` alone builds the
program's input type, because that is what the engine's entry takes.
"""

import hashlib
import struct

import numpy as np

from .reference import sort_rows

KEY_BYTES = 2 + 16 + 8          # u16 BE hashkey length + hashkey + sortkey
VALUE_HEADER = 13               # v2 value header: 0x82, expire u32 BE, 8 B


# ------------------------------------------------------------- bulk fill


def make_run(n: int, value_size: int, rng, key_space: int, sortkeys,
             ttl_frac: float, del_frac: float) -> dict:
    """n fillrandom records as plain arrays: keys (n, 26) u8, vals
    (n, 13 + value_size) u8, expire u32 (1..49 on a ttl_frac share, else
    0), deleted bool (a del_frac share). Hashkeys come from a bounded
    space and sortkeys from the few rows of `sortkeys`, so the same key
    is written again in later runs and there is dedup work (bench.py
    drew 8 random sortkey bytes: no key ever met an older version)."""
    keys = np.zeros((n, KEY_BYTES), dtype=np.uint8)
    keys[:, 1] = 16
    v = rng.integers(0, key_space, size=n)
    keys[:, 2:10] = np.frombuffer(b"userhash", dtype=np.uint8)
    for j in range(17, 9, -1):
        keys[:, j] = 48 + (v % 10)
        v //= 10
    keys[:, 18:26] = sortkeys[rng.integers(0, len(sortkeys), size=n)]

    vals = rng.integers(0, 256, size=(n, VALUE_HEADER + value_size),
                        dtype=np.uint8)
    expire = np.zeros(n, np.uint32)
    with_ttl = rng.random(n) < ttl_frac
    expire[with_ttl] = rng.integers(1, 50, size=int(with_ttl.sum()),
                                    dtype=np.uint32)
    vals[:, 0] = 0x82
    for j, shift in enumerate((24, 16, 8, 0)):
        vals[:, 1 + j] = (expire >> shift).astype(np.uint8)
    vals[:, 5:13] = 0
    deleted = rng.random(n) < del_frac
    return {"keys": keys, "vals": vals, "expire": expire, "deleted": deleted}


def presort_run(run: dict) -> dict:
    """A run as an L0 file is born: sorted by key, one record per key
    (the first writer of a key within the run wins)."""
    order = sort_rows(run["keys"])
    k = run["keys"][order]
    uniq = np.ones(len(order), dtype=bool)
    uniq[1:] = (k[1:] != k[:-1]).any(axis=1)
    keep = order[uniq]
    return {name: col[keep] for name, col in run.items()}


def fill_runs(seed: int, fill: dict) -> list:
    """The cell's sorted runs, oldest first, from the seed and the
    configuration's `fill` block."""
    per = fill["records"] // fill["runs"]
    sortkeys = np.random.default_rng([seed, fill["runs"]]).integers(
        0, 256, size=(fill["sortkeys_per_hashkey"], 8), dtype=np.uint8)
    return [presort_run(make_run(
        per, fill["value_bytes"], np.random.default_rng([seed, s]),
        max(1, int(fill["records"] * fill["hashkey_space_share"])), sortkeys,
        fill["ttl_expired_share"], fill["tombstone_share"]))
        for s in range(fill["runs"])]


def to_kvblock(run: dict):
    """The engine's input type over the same bytes (hash32 is the
    engine's own routing hash of the hashkey, as bench.py fills it)."""
    from pegasus_tpu.base.crc64 import crc64_batch
    from pegasus_tpu.engine.block import KVBlock

    n, klen = run["keys"].shape
    vlen = run["vals"].shape[1]
    flat = np.ascontiguousarray(run["keys"]).reshape(-1)
    hashes = crc64_batch(flat, np.arange(n, dtype=np.int64) * klen + 2,
                         np.full(n, 16, np.int64))
    return KVBlock(
        key_arena=flat,
        key_off=np.arange(n, dtype=np.int64) * klen,
        key_len=np.full(n, klen, np.int32),
        val_arena=np.ascontiguousarray(run["vals"]).reshape(-1),
        val_off=np.arange(n, dtype=np.int64) * vlen,
        val_len=np.full(n, vlen, np.int32),
        expire_ts=run["expire"],
        hash32=(hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        deleted=run["deleted"])


# --------------------------------------------------------- served records

VALUE_TAG = struct.Struct(">4sQIQ")   # magic, record, writer, sequence


def sortkey(seed: int, i: int) -> bytes:
    """8 B, spread over the whole byte range."""
    return hashlib.blake2b(b"%d:%d" % (seed, i), digest_size=8).digest()


def record_key(seed: int, i: int, sortkeys: int):
    """-> (16 B hashkey, 8 B sortkey) of record i."""
    return b"userhash%08d" % (i // sortkeys), sortkey(seed, i)


def make_value(seed: int, i: int, writer: int, seq: int, size: int) -> bytes:
    """The value writer `writer` stores in record i with its `seq`-th
    update (writer 0, seq 0 is the load): a 24 B tag that says so, then
    bytes only (seed, i, writer, seq) give. Any reader can check any
    answer on its own with `check_value`."""
    tag = VALUE_TAG.pack(b"pgv1", i, writer, seq)
    body = hashlib.shake_128(b"%d:%d:%d:%d" % (seed, i, writer, seq)).digest(
        size - len(tag))
    return tag + body


def check_value(seed: int, i: int, value, size: int):
    """-> (writer, seq) when `value` is a whole value some writer made
    for record i, else None."""
    if value is None or len(value) != size or value[:4] != b"pgv1":
        return None
    _, rec, writer, seq = VALUE_TAG.unpack_from(value)
    if rec != i or value != make_value(seed, i, writer, seq, size):
        return None
    return writer, seq


class ZipfKeys:
    """YCSB's zipfian rank generator (Gray et al., SIGMOD '94): ranks over
    [0, n) with P(rank k) ~ 1/(k+1)^theta; `scrambled` spreads the ranks
    over the records by a hash, as YCSB's ScrambledZipfianGenerator."""

    def __init__(self, n: int, theta: float = 0.99):
        self.n = n
        self.zetan = float(np.sum(1.0 / np.arange(1, n + 1) ** theta))
        self.zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1.0 - (2.0 / n) ** (1.0 - theta))
                    / (1.0 - self.zeta2 / self.zetan))

    def ranks(self, rng, count: int) -> np.ndarray:
        u = rng.random(count)
        uz = u * self.zetan
        r = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha
             ).astype(np.int64)
        r = np.minimum(r, self.n - 1)
        r[uz < self.zeta2] = 1
        r[uz < 1.0] = 0
        return r

    def scrambled(self, rng, count: int) -> np.ndarray:
        """Record numbers: the rank's 64-bit FNV-1a hash modulo n."""
        r = self.ranks(rng, count).astype(np.uint64)
        h = np.full(count, 0xCBF29CE484222325, np.uint64)
        with np.errstate(over="ignore"):
            for shift in range(0, 64, 8):
                h ^= (r >> np.uint64(shift)) & np.uint64(0xFF)
                h *= np.uint64(0x100000001B3)
        return (h % np.uint64(self.n)).astype(np.int64)
