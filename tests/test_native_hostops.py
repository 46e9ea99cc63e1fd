"""Differential tests: native hostops (C++, ctypes) vs the numpy
fallbacks. crc64 is the PARTITION HASH — a native/numpy divergence would
route the same key to different partitions depending on whether a host
could compile the library, silently splitting a table's data."""

import numpy as np
import pytest

from pegasus_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native hostops unavailable")


def _arena(keys):
    arena = np.frombuffer(b"".join(keys), dtype=np.uint8).copy()
    lens = np.array([len(k) for k in keys], np.int64)
    offs = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
    return arena, offs, lens


@pytest.mark.parametrize("seed", [0, 1])
def test_crc64_native_matches_numpy(seed):
    from pegasus_tpu.base.crc64 import crc64_batch_numpy

    rng = np.random.default_rng(seed)
    keys = [rng.bytes(int(rng.integers(0, 60))) for _ in range(500)]
    keys += [b"", b"\x00", b"a" * 255]
    arena, offs, lens = _arena(keys)
    want = crc64_batch_numpy(arena, offs, lens)
    got = native.crc64_batch(arena, offs, lens)
    assert np.array_equal(got, want)


def test_pack_prefixes_native_matches_numpy():
    from pegasus_tpu.ops import packing

    rng = np.random.default_rng(3)
    keys = [rng.bytes(int(rng.integers(1, 50))) for _ in range(300)]
    arena, offs, lens = _arena(keys)
    lens32 = lens.astype(np.int32)
    for w in (1, 4, 8):
        got = native.pack_prefixes(arena, offs, lens32, w)
        # the numpy fallback lives inside pack_key_prefixes' else branch;
        # reproduce it directly
        pos = np.arange(w * 4, dtype=np.int64)
        idx = offs[:, None] + pos[None, :]
        valid = pos[None, :] < lens[:, None]
        b = np.where(valid, arena[np.minimum(idx, len(arena) - 1)],
                     0).astype(np.uint32)
        want = (
            (b[:, 0::4] << 24) | (b[:, 1::4] << 16)
            | (b[:, 2::4] << 8) | b[:, 3::4]
        ).astype(np.uint32)
        assert np.array_equal(np.asarray(got), want), w


def test_merge_counts_native_matches_searchsorted():
    rng = np.random.default_rng(5)
    for itemsize, na, nb in ((8, 400, 300), (16, 256, 256), (24, 100, 999)):
        a = np.sort(rng.integers(0, 1 << 62, size=na, dtype=np.int64)
                    .astype(f">u8").view(f"S8"))
        b = np.sort(rng.integers(0, 1 << 62, size=nb, dtype=np.int64)
                    .astype(f">u8").view(f"S8"))
        if itemsize != 8:
            reps = itemsize // 8
            a = np.sort(np.array([x * reps for x in a.tolist()],
                                 dtype=f"S{itemsize}"))
            b = np.sort(np.array([x * reps for x in b.tolist()],
                                 dtype=f"S{itemsize}"))
        for side in ("left", "right"):
            got = native.merge_counts(a, b, side)
            want = np.searchsorted(b, a, side=side)
            assert np.array_equal(got, want), (itemsize, side)


def test_gather_arena_native_matches_fancy_indexing():
    rng = np.random.default_rng(7)
    keys = [rng.bytes(int(rng.integers(0, 40))) for _ in range(200)]
    arena, offs, lens = _arena(keys)
    lens32 = lens.astype(np.int32)
    idx = rng.permutation(200)[:120].astype(np.int64)
    out, out_off = native.gather_arena(arena, offs, lens32, idx)
    want = b"".join(keys[i] for i in idx)
    assert out.tobytes() == want
    assert np.array_equal(out_off,
                          np.concatenate([[0], np.cumsum(lens32[idx][:-1])]))


# ------------------------------------------------------- by-run gather


def _uniform_run(rng, n, klen, vlen):
    from pegasus_tpu.engine.block import KVBlock

    return KVBlock(
        rng.integers(0, 256, size=n * klen, dtype=np.uint8),
        np.arange(n, dtype=np.int64) * klen, np.full(n, klen, np.int32),
        rng.integers(0, 256, size=n * vlen, dtype=np.uint8),
        np.arange(n, dtype=np.int64) * vlen, np.full(n, vlen, np.int32),
        rng.integers(0, 1000, size=n, dtype=np.uint32),
        rng.integers(0, 1 << 32, size=n, dtype=np.uint32),
        rng.random(n) < 0.2)


def _gather_by_run(runs, klen, vlen, idx, use_native, with_vals=True):
    m = len(idx)
    out_k = np.zeros((m, klen), np.uint8)
    out_v = np.zeros((m, vlen), np.uint8) if with_vals else None
    out_e = np.zeros(m, np.uint32)
    out_h = np.zeros(m, np.uint32)
    out_d = np.zeros(m, np.bool_)
    native.gather_runs_uniform(runs, klen, vlen, idx, out_k, out_v, out_e,
                               out_h, out_d, use_native=use_native)
    return out_k, out_v, out_e, out_h, out_d


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "numpy_twin"])
@pytest.mark.parametrize("k", [1, 4, 9, 40])
def test_gather_runs_uniform_matches_concat_gather(k, use_native):
    """The by-run gather (native loop and numpy twin; the counted scan at
    K <= 8, the binary search above) equals KVBlock.concat(runs).gather(idx)
    column for column, first and last row of every run among the indices;
    the keys-only half leaves the values alone."""
    from pegasus_tpu.engine.block import KVBlock

    rng = np.random.default_rng(100 + k)
    klen, vlen = 12, 40
    runs = [_uniform_run(rng, int(rng.integers(1, 90)), klen, vlen)
            for _ in range(k)]
    starts = np.cumsum([0] + [r.n for r in runs])
    edges = np.concatenate([starts[:-1], starts[1:] - 1])
    idx = np.concatenate([edges, rng.integers(0, starts[-1], size=300)])
    idx = rng.permutation(idx).astype(np.int32)
    want = KVBlock.concat(runs).gather(idx)
    out_k, out_v, out_e, out_h, out_d = _gather_by_run(
        runs, klen, vlen, idx, use_native)
    assert np.array_equal(out_k.reshape(-1), want.key_arena)
    assert np.array_equal(out_v.reshape(-1), want.val_arena)
    assert np.array_equal(out_e, want.expire_ts)
    assert np.array_equal(out_h, want.hash32)
    assert np.array_equal(out_d, want.deleted)
    out_k, out_v, out_e, out_h, out_d = _gather_by_run(
        runs, klen, vlen, idx, use_native, with_vals=False)
    assert out_v is None
    assert np.array_equal(out_k.reshape(-1), want.key_arena)
    assert np.array_equal(out_e, want.expire_ts)
    assert np.array_equal(out_h, want.hash32)
    assert np.array_equal(out_d, want.deleted)


@pytest.mark.parametrize("use_native", [True, False],
                         ids=["native", "numpy_twin"])
@pytest.mark.parametrize("bad", [-1, "total", 1 << 40])
def test_gather_runs_uniform_refuses_out_of_range_index(bad, use_native):
    """The 'device pipeline bug' check, against the runs' total: an index
    outside [0, total) raises before any pointer arithmetic (an int64 one
    before it could wrap into range as int32)."""
    rng = np.random.default_rng(5)
    runs = [_uniform_run(rng, n, 8, 16) for n in (7, 3, 11)]
    total = sum(r.n for r in runs)
    idx = np.array([0, total - 1, total if bad == "total" else bad],
                   np.int64)
    with pytest.raises(ValueError, match="device pipeline bug"):
        _gather_by_run(runs, 8, 16, idx, use_native)
    # in range, the same call gathers
    _gather_by_run(runs, 8, 16, idx[:2], use_native)
