"""The plain reference: what the system has to answer, worked out with
numpy sorts and ordinary dict logic. Imports nothing of the program and
takes nothing the program has made.
"""

import numpy as np


def sort_rows(keys: np.ndarray, newest_first=None) -> np.ndarray:
    """Stable order of fixed-width byte rows as memcmp sorts them; ties
    broken by `newest_first` (smaller sorts first) when given."""
    n, w = keys.shape
    words = -(-w // 8)
    padded = np.zeros((n, words * 8), np.uint8)
    padded[:, :w] = keys
    cols = padded.view(">u8")
    by = [cols[:, j] for j in range(words - 1, -1, -1)]
    if newest_first is not None:
        by.insert(0, newest_first)
    return np.lexsort(by)


def compact(runs: list, now: int, keep: str = "newest",
            drop_expired: bool = True) -> dict:
    """Full compaction to the bottom level of `runs` (oldest first; a run
    ingested later is newer): for each key its newest version, unless that
    version is a tombstone or its TTL has passed (0 < expire <= now) — then
    nothing. Sorted by key. `keep="oldest"` and `drop_expired=False` each
    break one of those guarantees: the controls."""
    age = np.concatenate([np.full(len(r["keys"]), len(runs) - 1 - j, np.int64)
                          for j, r in enumerate(runs)])
    if keep == "oldest":
        age = -age
    cat = {name: np.concatenate([r[name] for r in runs])
           for name in ("keys", "vals", "expire", "deleted")}
    order = sort_rows(cat["keys"], newest_first=age)
    k = cat["keys"][order]
    first = np.ones(len(order), dtype=bool)
    first[1:] = (k[1:] != k[:-1]).any(axis=1)
    win = order[first]
    expire = cat["expire"][win]
    live = ~cat["deleted"][win]
    if drop_expired:
        live &= ~((expire > 0) & (expire <= now))
    win = win[live]
    return {"keys": cat["keys"][win], "vals": cat["vals"][win],
            "expire": cat["expire"][win],
            "input_records": int(len(order))}


def differing_rows(want: dict, got: dict) -> int:
    """How many output records differ between two compaction outputs given
    as flat key / value byte arrays plus expire columns: the rows of the
    longer that have no equal row at the same place in the other."""
    n_want, n_got = len(want["expire"]), len(got["expire"])
    n = min(n_want, n_got)
    bad = np.zeros(n, dtype=bool)
    for name in ("keys", "vals"):
        a = want[name].reshape(n_want, -1)[:n] if n_want else want[name]
        b = got[name].reshape(n_got, -1)[:n] if n_got else got[name]
        if n and a.shape[1] != b.shape[1]:
            return max(n_want, n_got)
        if n:
            bad |= (a != b).any(axis=1)
    if n:
        bad |= want["expire"][:n] != got["expire"][:n]
    return int(bad.sum()) + abs(n_want - n_got)


def point_answers(runs: list, now: int, keys: np.ndarray) -> list:
    """What a point read of each of `keys` (rows of key bytes) must return
    after the fill: the newest version's value bytes, or None when there
    is none, it is a tombstone, or it has expired. Ordinary dict logic."""
    want = {bytes(k) for k in keys}
    newest = {}
    for run in runs:                       # oldest first: later overwrite
        hit = np.flatnonzero(np.isin(
            _row_ids(run["keys"]), _row_ids(keys)))
        for j in hit:
            kb = bytes(run["keys"][j])
            if kb in want:
                newest[kb] = (bytes(run["vals"][j]), int(run["expire"][j]),
                              bool(run["deleted"][j]))
    out = []
    for k in keys:
        v = newest.get(bytes(k))
        if v is None or v[2] or (0 < v[1] <= now):
            out.append(None)
        else:
            out.append(v[0])
    return out


def _row_ids(keys: np.ndarray) -> np.ndarray:
    """Rows of bytes as one void scalar each, for set membership."""
    k = np.ascontiguousarray(keys)
    return k.view(np.dtype((np.void, k.shape[1]))).reshape(-1)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by nearest rank over all values."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if len(v) == 0:
        return None
    return float(v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)])


class ReferenceStore:
    """A plain key-value table behind the client's get / set / batch_get:
    a dict under a lock. Put in the served system's place it is the
    control: `stale_reads` answers a get with the version before the
    newest (a read that does not see an acknowledged write), `lose_every`
    acknowledges every n-th set without storing it (an acknowledged write
    that is on no replica), `alter_every` flips a byte of every n-th
    answer. With none of them it keeps every guarantee."""

    def __init__(self, stale_reads: bool = False, lose_every: int = 0,
                 alter_every: int = 0):
        import threading

        self._lock = threading.Lock()
        self._rows, self._before = {}, {}
        self.stale_reads, self.lose_every = stale_reads, lose_every
        self.alter_every = alter_every
        self._sets = self._gets = 0

    def set(self, hash_key: bytes, sort_key: bytes, value: bytes) -> None:
        with self._lock:
            self._sets += 1
            if self.lose_every and self._sets % self.lose_every == 0:
                return
            key = (hash_key, sort_key)
            if key in self._rows:
                self._before[key] = self._rows[key]
            self._rows[key] = value

    def get(self, hash_key: bytes, sort_key: bytes):
        with self._lock:
            self._gets += 1
            key = (hash_key, sort_key)
            value = self._rows.get(key)
            if self.stale_reads:
                value = self._before.get(key, value)
            if (value is not None and self.alter_every
                    and self._gets % self.alter_every == 0):
                value = value[:-1] + bytes([value[-1] ^ 1])
            return value

    def batch_get(self, items: list) -> list:
        return [self.get(hk, sk) for hk, sk in items]

    def close(self) -> None:
        pass
