"""Compaction kernel tests: semantics + cpu/tpu differential (bit-stability).

The tpu backend runs on the test harness's virtual CPU devices; semantics and
output bytes must match the numpy cpu backend exactly (SURVEY.md §7d).
"""

import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key, key_hash
from pegasus_tpu.base.value_schema import SCHEMAS
from pegasus_tpu.engine.block import KVBlock
from pegasus_tpu.ops import CompactOptions, compact_blocks, sort_block
from pegasus_tpu.ops.packing import compute_suffix_ranks, pack_key_prefixes


def make_block(records):
    """records: (hash_key, sort_key, payload, expire, deleted)"""
    rows = []
    for hk, sk, payload, expire, deleted in records:
        key = generate_key(hk, sk)
        val = b"" if deleted else SCHEMAS[2].generate_value(expire, 0, payload)
        rows.append((key, val, expire, deleted))
    return KVBlock.from_records(rows)


def keys_of(block):
    return list(block.keys())


def test_sort_block_orders_by_key_bytes():
    recs = [(f"hk{i%7}".encode(), f"sk{i:03d}".encode(), b"v", 0, False) for i in range(50)]
    np.random.default_rng(1).shuffle(recs)
    out = sort_block(make_block(recs), CompactOptions(backend="cpu"))
    ks = keys_of(out)
    assert ks == sorted(ks)
    assert out.n == 50


def test_dedup_newest_run_wins():
    newest = make_block([(b"h", b"s", b"NEW", 0, False)])
    oldest = make_block([(b"h", b"s", b"OLD", 0, False), (b"h", b"t", b"KEEP", 0, False)])
    res = compact_blocks([newest, oldest], CompactOptions(backend="cpu", now=100))
    assert res.block.n == 2
    vals = [res.block.value(i) for i in range(2)]
    assert SCHEMAS[2].extract_user_data(vals[0]) == b"NEW"
    assert SCHEMAS[2].extract_user_data(vals[1]) == b"KEEP"


def test_ttl_expiry_dropped_only_when_filtering():
    blk = make_block([
        (b"h", b"alive", b"v", 1000, False),
        (b"h", b"dead", b"v", 50, False),
        (b"h", b"nottl", b"v", 0, False),
    ])
    res = compact_blocks([blk], CompactOptions(backend="cpu", now=100))
    assert {k for k in (generate_key(b"h", s) for s in (b"alive", b"nottl"))} == set(keys_of(res.block))
    # flush path keeps expired records
    out = sort_block(blk, CompactOptions(backend="cpu", now=100))
    assert out.n == 3


def test_tombstones_dropped_only_at_bottommost():
    newest = make_block([(b"h", b"s", b"", 0, True)])  # delete marker
    oldest = make_block([(b"h", b"s", b"OLD", 0, False)])
    bottom = compact_blocks([newest, oldest], CompactOptions(backend="cpu", now=1, bottommost=True))
    assert bottom.block.n == 0  # tombstone consumed the old version and itself
    mid = compact_blocks([newest, oldest], CompactOptions(backend="cpu", now=1, bottommost=False))
    assert mid.block.n == 1  # tombstone survives to keep masking lower levels
    assert mid.block.deleted[0]


def test_split_stale_keys_gc():
    recs = [(f"k{i}".encode(), b"", b"v", 0, False) for i in range(64)]
    blk = make_block(recs)
    mask, pidx = 3, 2
    res = compact_blocks([blk], CompactOptions(backend="cpu", now=1, pidx=pidx, partition_mask=mask))
    for k in keys_of(res.block):
        assert (key_hash(k) & mask) == pidx
    expect = sum(1 for hk, _, _, _, _ in recs if key_hash(generate_key(hk, b"")) & mask == pidx)
    assert res.block.n == expect > 0


def test_default_ttl_rewrite():
    blk = make_block([(b"h", b"a", b"v", 0, False), (b"h", b"b", b"v", 500, False)])
    res = compact_blocks([blk], CompactOptions(backend="cpu", now=100, default_ttl=50))
    by_key = {res.block.key(i): i for i in range(res.block.n)}
    ia = by_key[generate_key(b"h", b"a")]
    assert res.block.expire_ts[ia] == 150  # now + default_ttl
    # value header rewritten too (v2: expire at offset 1)
    assert SCHEMAS[2].extract_expire_ts(res.block.value(ia)) == 150
    ib = by_key[generate_key(b"h", b"b")]
    assert res.block.expire_ts[ib] == 500


def test_default_ttl_short_value_guarded():
    """Regression: the 4-byte BE TTL rewrite must SKIP records whose value
    is shorter than the expire field itself (has_hdr only guarded the
    READ) — rewriting them scribbled into the neighboring record's arena
    bytes, or past the arena end for the last record."""
    from pegasus_tpu.ops.compact import _apply_default_ttl

    good_val = SCHEMAS[2].generate_value(0, 0, b"payload")
    blk = KVBlock.from_records([
        (b"\x00\x01a", b"\x01\x02", 0, False),   # 2B value: can't hold a TTL
        (b"\x00\x01b", good_val, 0, False),
    ])
    neighbor_before = bytes(blk.val_arena[blk.val_off[1]:
                                          blk.val_off[1] + blk.val_len[1]])
    _apply_default_ttl(blk, 777)
    # the short record was skipped entirely: bytes AND column untouched
    assert bytes(blk.val_arena[blk.val_off[0]:
                               blk.val_off[0] + blk.val_len[0]]) == b"\x01\x02"
    assert blk.expire_ts[0] == 0
    # the neighbor got its own rewrite, not the short record's overflow
    assert blk.expire_ts[1] == 777
    assert SCHEMAS[2].extract_expire_ts(
        bytes(blk.val_arena[blk.val_off[1]:
                            blk.val_off[1] + blk.val_len[1]])) == 777
    assert neighbor_before != bytes(
        blk.val_arena[blk.val_off[1]:blk.val_off[1] + blk.val_len[1]])
    # last-record overflow: a lone short value must not crash or write
    # past the arena end
    solo = KVBlock.from_records([(b"\x00\x01c", b"\x01", 0, False)])
    _apply_default_ttl(solo, 777)
    assert solo.expire_ts[0] == 0 and bytes(solo.val_arena[
        solo.val_off[0]:solo.val_off[0] + solo.val_len[0]]) == b"\x01"


def _adversarial_records(rng, n):
    """Keys engineered to stress prefix windows: a shared prefix longer
    than the window's 64-byte cap (the suffix-rank path), trailing zeros,
    strict-prefix pairs, empty hash/sort keys, 62-byte keys (the widest
    windows that still hold a whole key)."""
    recs = []
    long_prefix = b"P" * 72
    for i in range(n):
        mode = i % 6
        if mode == 0:
            hk, sk = rng.bytes(4), rng.bytes(rng.integers(0, 6))
        elif mode == 1:  # keys over the cap sharing a 72-byte prefix
            hk, sk = long_prefix, rng.bytes(rng.integers(0, 8))
        elif mode == 2:  # trailing zero bytes
            hk, sk = b"z", b"\x00" * rng.integers(0, 5)
        elif mode == 3:  # strict prefix pairs
            hk, sk = b"pre", b"fix"[: rng.integers(0, 4)]
        elif mode == 4:  # empty hash key
            hk, sk = b"", rng.bytes(3)
        else:
            hk, sk = rng.bytes(30), rng.bytes(30)
        expire = int(rng.integers(0, 200))
        deleted = bool(rng.random() < 0.15)
        recs.append((hk, sk, b"payload%d" % i, expire, deleted))
    return recs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cpu_tpu_differential_bitstable(seed):
    rng = np.random.default_rng(seed)
    runs = [make_block(_adversarial_records(rng, 200)) for _ in range(3)]
    opts = dict(now=100, pidx=1, partition_mask=1, bottommost=(seed % 2 == 0), default_ttl=30)
    r_cpu = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    r_tpu = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    assert r_cpu.block.n == r_tpu.block.n
    np.testing.assert_array_equal(r_cpu.block.key_arena, r_tpu.block.key_arena)
    np.testing.assert_array_equal(r_cpu.block.val_arena, r_tpu.block.val_arena)
    np.testing.assert_array_equal(r_cpu.block.expire_ts, r_tpu.block.expire_ts)
    np.testing.assert_array_equal(r_cpu.block.deleted, r_tpu.block.deleted)
    # output is sorted, unique, and semantically correct
    ks = keys_of(r_cpu.block)
    assert ks == sorted(ks) and len(ks) == len(set(ks))


def test_cpu_output_matches_python_reference_model():
    """Model-based check: brute-force dict semantics == kernel output."""
    rng = np.random.default_rng(7)
    runs = [make_block(_adversarial_records(rng, 150)) for _ in range(4)]
    now, pidx, pmask = 100, 0, 1
    res = compact_blocks(runs, CompactOptions(backend="cpu", now=now, pidx=pidx,
                                              partition_mask=pmask, bottommost=True))
    # brute force: newest run wins per key; then filter
    model = {}
    for b in runs:  # newest first; first writer wins
        for i in range(b.n):
            model.setdefault(b.key(i), (b.value(i), int(b.expire_ts[i]), bool(b.deleted[i])))
    expect = []
    for k, (v, exp, dead) in model.items():
        if dead or (0 < exp <= now):
            continue
        if (key_hash(k) & pmask) != pidx:
            continue
        expect.append(k)
    assert sorted(expect) == keys_of(res.block)


def test_prefix_collision_suffix_ranks():
    base = b"C" * 68          # over the default window's 64 bytes
    recs = [(base, bytes([b]), b"v", 0, False) for b in [3, 1, 2, 0xFF, 0]]
    recs.append((base, b"", b"v", 0, False))  # strict prefix of the others
    blk = make_block(recs)
    ranks = compute_suffix_ranks(blk)
    out = sort_block(blk, CompactOptions(backend="cpu"))
    ks = keys_of(out)
    assert ks == sorted(ks)
    assert out.n == 6


@pytest.mark.parametrize("n,ncols", [(64, 1), (1024, 3), (4096, 9)])
def test_sort_network_matches_lexsort(n, ncols):
    import jax
    import jax.numpy as jnp

    from pegasus_tpu.ops.device_sort import sort_network

    rng = np.random.default_rng(n + ncols)
    # small value range to force cross-column ties
    cols = [rng.integers(0, 7, size=n, dtype=np.uint32) for _ in range(ncols)]
    out = jax.jit(lambda c: sort_network(c, nk=ncols))(
        [jnp.asarray(c) for c in cols] + [jnp.arange(n, dtype=jnp.int32)]
    )
    want = np.lexsort(tuple(reversed(cols)))
    for c, g in zip(cols, out[:ncols]):
        np.testing.assert_array_equal(np.asarray(g), c[want])
    # permutation is a valid reordering producing the sorted columns
    perm = np.asarray(out[-1])
    assert sorted(perm) == list(range(n))
    for c, g in zip(cols, out[:ncols]):
        np.testing.assert_array_equal(c[perm], np.asarray(g))


@pytest.mark.parametrize("la,lb", [(100, 100), (1, 37), (500, 12), (1024, 1024)])
def test_merge_two_sorted_runs(la, lb):
    import jax
    import jax.numpy as jnp

    from pegasus_tpu.ops.device_sort import merge_two_sorted

    rng = np.random.default_rng(la * 1000 + lb)
    ncols = 3

    def mk(n):
        prim = np.sort(rng.integers(0, 50, size=n, dtype=np.uint32))
        rest = [rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
                for _ in range(ncols - 1)]
        # make rows unique & sorted via lexsort on all cols
        order = np.lexsort(tuple(reversed([prim] + rest)))
        return [c[order] for c in [prim] + rest]

    A, B = mk(la), mk(lb)
    pad_fill = tuple([np.uint32(0xFFFFFFFF)] * ncols + [np.int32(-1)])
    a_ops = [jnp.asarray(c) for c in A] + [jnp.arange(la, dtype=jnp.int32)]
    b_ops = [jnp.asarray(c) for c in B] + [jnp.arange(la, la + lb, dtype=jnp.int32)]
    out = jax.jit(lambda a, b: merge_two_sorted(a, b, ncols, pad_fill))(a_ops, b_ops)
    merged = [np.asarray(c)[: la + lb] for c in out]
    want_cols = [np.concatenate([a, b]) for a, b in zip(A, B)]
    want = np.lexsort(tuple(reversed(want_cols)))
    for wc, g in zip(want_cols, merged[:ncols]):
        np.testing.assert_array_equal(g, wc[want])
    assert sorted(np.asarray(merged[-1])) == list(range(la + lb))


def test_pack_prefix_bigendian_order():
    blk = make_block([(b"ab", b"", b"v", 0, False), (b"ac", b"", b"v", 0, False)])
    p = pack_key_prefixes(blk.key_arena, blk.key_off, blk.key_len, 2)
    # big-endian packing preserves byte order in u32 comparison
    assert p[0, 0] < p[1, 0]
    # key bytes \x00\x02ab -> 0x000261 62
    assert p[0, 0] == 0x00026162
    assert p[0, 1] == 0  # zero padding


def test_wide_merge_over_255_runs_chunks_correctly():
    """Run priority travels in 8 bits; >255 runs pre-combine (newest-first)
    without filtering so the final semantics are unchanged."""
    runs = []
    for i in range(300):
        runs.append(make_block([(b"shared", b"", b"run%d" % i, 0, False),
                                (b"only%d" % i, b"", b"v", 0, False)]))
    res = compact_blocks(runs, CompactOptions(backend="cpu", now=1))
    assert res.block.n == 301
    by_key = {res.block.key(i): res.block.value(i) for i in range(res.block.n)}
    from pegasus_tpu.base.value_schema import SCHEMAS
    assert SCHEMAS[2].extract_user_data(by_key[generate_key(b"shared", b"")]) == b"run0"


def test_pow2_bucketing_bounds_recompiles():
    """VERDICT-r2 weak 9: a pathological flush pattern (many distinct run
    sizes) must not mean one device compile per size — pow2 bucket padding
    maps nearby lengths onto the same jitted pipeline."""
    from pegasus_tpu.ops.compact import (CompactOptions, _compiled_pipeline,
                                         compact_blocks)

    _compiled_pipeline.cache_clear()
    rng = np.random.default_rng(11)
    for n in (300, 311, 342, 401, 477, 509):  # all in the (256, 512] bucket
        recs = [(b"h%d" % i, b"s%d" % (rng.integers(0, 1000)), b"v", 0, False)
                for i in range(n)]
        runs = [make_block(recs[: n // 2]), make_block(recs[n // 2:])]
        compact_blocks(runs, CompactOptions(backend="tpu", now=100))
    info = _compiled_pipeline.cache_info()
    # every distinct-size merge after the first reused the compiled program
    assert info.misses <= 2, f"recompiled per size: {info}"
    assert info.hits >= 4, f"no cache reuse: {info}"


def test_device_run_cache_matches_host_pack_path():
    """VERDICT-r2 item 4: compaction over cached DeviceRuns (the engine's
    HBM-resident path — no host pack, no re-upload) must be byte-identical
    to the host-packed tpu path AND the cpu lane."""
    from pegasus_tpu.ops.compact import (CompactOptions, compact_blocks,
                                         pack_run_device)

    rng = np.random.default_rng(29)
    recs = []
    for i in range(900):
        hk = b"u%05d" % rng.integers(0, 400)
        deleted = bool(rng.random() < 0.1)
        expire = int(rng.integers(0, 3)) * 60
        recs.append((hk, b"s%02d" % (i % 7), b"" if deleted else b"val%d" % i,
                     expire, deleted))
    # three sorted non-overlapping-free runs (dups across runs)
    from tests.test_compact_ops import make_block

    runs = []
    for part in (recs[:300], recs[300:600], recs[600:]):
        blk = make_block(sorted(set(part), key=lambda r: (len(r[0]), r[0], r[1])))
        # make_block inputs must be sorted by encoded key: easier to sort
        # the block through the flush path
        from pegasus_tpu.ops.compact import sort_block

        runs.append(sort_block(blk, CompactOptions(backend="cpu")))
    opts = dict(now=100, bottommost=True, runs_sorted=True)
    cpu = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    host = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    device_runs = [pack_run_device(b) for b in runs]
    assert all(d is not None for d in device_runs)
    cached = compact_blocks(runs, CompactOptions(backend="tpu", **opts),
                            device_runs=device_runs)
    for other in (host, cached):
        assert other.block.n == cpu.block.n
        np.testing.assert_array_equal(cpu.block.key_arena, other.block.key_arena)
        np.testing.assert_array_equal(cpu.block.val_arena, other.block.val_arena)
        np.testing.assert_array_equal(cpu.block.expire_ts, other.block.expire_ts)


def test_engine_tpu_backend_uses_device_cache(tmp_path):
    """An engine on backend=tpu serves identical data to a cpu engine, and
    its SSTs hold primed device runs after flush."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import SCHEMAS
    from pegasus_tpu.engine import EngineOptions, LsmEngine

    engines = {}
    for backend in ("cpu", "tpu"):
        eng = LsmEngine(str(tmp_path / backend), EngineOptions(
            backend=backend, memtable_bytes=8 << 10,
            l0_compaction_trigger=3))
        for i in range(400):
            key = generate_key(b"h%d" % (i % 37), b"s%05d" % i)
            eng.put(key, SCHEMAS[2].generate_value(0, 0, b"v%d" % i))
            if i % 90 == 89:
                eng.delete(generate_key(b"h%d" % (i % 37), b"s%05d" % i))
        eng.manual_compact(now=100)
        engines[backend] = eng
    tpu = engines["tpu"]
    # flush/compaction outputs were primed into the device cache
    primed = [s for s in tpu._l0 + sum(tpu._levels.values(), [])
              if s._device_run is not None]
    assert primed, "no SST holds a device-resident run"
    for i in range(400):
        key = generate_key(b"h%d" % (i % 37), b"s%05d" % i)
        assert engines["cpu"].get(key) == tpu.get(key), f"diverged at {i}"
    for eng in engines.values():
        eng.close()


def test_device_cache_pipeline_shares_programs_across_sizes():
    """The cached-run pipeline must be keyed on pow2 buckets, not exact run
    lengths: distinct sizes in one bucket share one compiled program."""
    from pegasus_tpu.ops.compact import (CompactOptions,
                                         _compiled_pipeline_cached,
                                         compact_blocks, pack_run_device,
                                         sort_block)

    _compiled_pipeline_cached.cache_clear()
    rng = np.random.default_rng(31)
    outs = []
    for n in (300, 333, 410, 489):  # all in the (256, 512] bucket
        recs = [(b"h%03d" % rng.integers(0, 200), b"s%d" % i, b"v%d" % i,
                 0, False) for i in range(n)]
        runs = [sort_block(make_block(recs[: n // 2]),
                           CompactOptions(backend="cpu")),
                sort_block(make_block(recs[n // 2:]),
                           CompactOptions(backend="cpu"))]
        device_runs = [pack_run_device(b) for b in runs]
        opts = CompactOptions(backend="tpu", now=100, runs_sorted=True)
        got = compact_blocks(runs, opts, device_runs=device_runs)
        want = compact_blocks(runs, CompactOptions(backend="cpu", now=100,
                                                   runs_sorted=True))
        np.testing.assert_array_equal(want.block.key_arena, got.block.key_arena)
        np.testing.assert_array_equal(want.block.val_arena, got.block.val_arena)
        outs.append(got.block.n)
    info = _compiled_pipeline_cached.cache_info()
    assert info.misses == 1, f"recompiled per size: {info}"
    # (a cold kernel's first call re-enters the builder once more after the
    # guard waited for its compile: one extra hit when nothing was cached)
    assert info.hits in (3, 4), f"no reuse: {info}"


def test_blockwise_merge_matches_whole_merge():
    """SURVEY §5.7 long-context analogue: a merge bigger than the device
    budget decomposes into disjoint key ranges whose outputs concatenate
    byte-equal to the whole-merge result — the bigger-than-HBM path."""
    from dataclasses import replace

    from pegasus_tpu.ops.compact import (CompactOptions, compact_blocks,
                                         sort_block)

    rng = np.random.default_rng(41)
    recs = []
    for i in range(4000):
        hk = b"u%06d" % rng.integers(0, 1500)
        deleted = bool(rng.random() < 0.08)
        expire = int(rng.integers(0, 3)) * 50
        recs.append((hk, b"s%d" % (i % 5), b"" if deleted else b"w%d" % i,
                     expire, deleted))
    runs = [sort_block(make_block(part), CompactOptions(backend="cpu"))
            for part in (recs[:1500], recs[1500:2600], recs[2600:])]
    base = CompactOptions(backend="tpu", now=60, runs_sorted=True)
    whole = compact_blocks(runs, base)
    for budget in (500, 1000, 2500):
        split = compact_blocks(runs, replace(base,
                                             max_device_records=budget))
        assert split.block.n == whole.block.n
        np.testing.assert_array_equal(whole.block.key_arena,
                                      split.block.key_arena)
        np.testing.assert_array_equal(whole.block.val_arena,
                                      split.block.val_arena)
        np.testing.assert_array_equal(whole.block.expire_ts,
                                      split.block.expire_ts)
    # degenerate distribution: every record shares one key — must not
    # recurse forever, and still dedups to a single survivor
    one = sort_block(make_block([(b"k", b"s", b"v%d" % i, 0, False)
                                 for i in range(50)]),
                     CompactOptions(backend="cpu"))
    same = [one, one]
    res = compact_blocks(same, replace(base, max_device_records=10))
    assert res.block.n == 1


def test_blockwise_merge_long_keys_rank_path():
    """Blockwise decomposition with keys beyond the prefix window (the
    suffix-rank pack path) must stay byte-equal — and compacts its range
    slices so the rank concat doesn't drag whole arenas per range."""
    from dataclasses import replace

    from pegasus_tpu.ops.compact import (CompactOptions, compact_blocks,
                                         sort_block)

    rng = np.random.default_rng(43)
    recs = []
    for i in range(1200):
        # 80+B hashkeys: longer than the window's cap, 4*16 = 64 bytes
        hk = b"verylonghashkeyprefix-%058d" % rng.integers(0, 400)
        recs.append((hk, b"s%d" % (i % 3), b"v%d" % i, 0, False))
    runs = [sort_block(make_block(part), CompactOptions(backend="cpu"))
            for part in (recs[:600], recs[600:])]
    base = CompactOptions(backend="tpu", now=60, runs_sorted=True)
    whole = compact_blocks(runs, base)
    split = compact_blocks(runs, replace(base, max_device_records=400))
    assert split.block.n == whole.block.n
    np.testing.assert_array_equal(whole.block.key_arena, split.block.key_arena)
    np.testing.assert_array_equal(whole.block.val_arena, split.block.val_arena)


def _uniform_runs(rng, n_runs=3, n=400):
    """Fixed-width records (the bench/engine fast layout): 8B hash keys,
    8B sort keys, width-10 payloads -> uniform_layout() is non-None."""
    runs = []
    for r in range(n_runs):
        recs = [(b"h%07d" % rng.integers(0, 120), b"s%07d" % rng.integers(0, 40),
                 b"p%09d" % rng.integers(0, 10**9), int(rng.integers(0, 150)),
                 bool(rng.random() < 0.2)) for _ in range(n)]
        # tombstones must keep the uniform value width (empty values would
        # break the fixed layout, as in the bench fill where tombstones
        # still carry a full-width value row)
        rows = []
        from pegasus_tpu.base.key_schema import generate_key
        from pegasus_tpu.base.value_schema import SCHEMAS

        for hk, sk, payload, expire, deleted in recs:
            rows.append((generate_key(hk, sk),
                         SCHEMAS[2].generate_value(expire, 0, payload),
                         expire, deleted))
        runs.append(sort_block(KVBlock.from_records(rows)))
    return runs


def test_materialize_device_survivors_matches_host_gather():
    """Value-residency materialization (device value gather + host key
    gather, overlapped) is byte-identical to the host fused gather."""
    from pegasus_tpu.ops.compact import (TpuBackend, gather_runs,
                                         materialize_device_survivors,
                                         pack_runs, prepare_values)

    rng = np.random.default_rng(3)
    runs = _uniform_runs(rng)
    opts = CompactOptions(backend="tpu", now=100, bottommost=True,
                          runs_sorted=True)
    packed = pack_runs(runs, opts, need_sbytes=False)
    backend = TpuBackend()
    prep = backend.prepare(packed)
    dev_idx, cnt = backend.survivors_device(prep, 100, 0, 0, True, True)
    assert cnt > 0
    concat = KVBlock.concat(runs)
    base = gather_runs([concat], dev_idx, cnt)
    dev_vals = prepare_values(concat)
    assert dev_vals is not None
    out = materialize_device_survivors(concat, dev_vals, dev_idx, cnt)
    assert out.n == base.n == cnt
    np.testing.assert_array_equal(base.key_arena, out.key_arena)
    np.testing.assert_array_equal(base.val_arena, out.val_arena)
    np.testing.assert_array_equal(base.expire_ts, out.expire_ts)
    np.testing.assert_array_equal(base.hash32, out.hash32)
    np.testing.assert_array_equal(base.deleted, out.deleted)
    np.testing.assert_array_equal(base.key_off, out.key_off)
    np.testing.assert_array_equal(base.val_off, out.val_off)


def test_materialize_device_survivors_nonuniform_falls_back():
    """Variable-width values: prepare_values declines, and the entry point
    degrades to the host gather instead of corrupting rows."""
    from pegasus_tpu.ops.compact import (TpuBackend, materialize_device_survivors,
                                         pack_runs, prepare_values)

    rng = np.random.default_rng(5)
    runs = [sort_block(make_block(_adversarial_records(rng, 150)))
            for _ in range(2)]
    concat = KVBlock.concat(runs)
    assert prepare_values(concat) is None
    opts = CompactOptions(backend="tpu", now=100, bottommost=True,
                          runs_sorted=True)
    packed = pack_runs(runs, opts, need_sbytes=False)
    backend = TpuBackend()
    dev_idx, cnt = backend.survivors_device(packed, 100, 0, 0, True, True)
    out = materialize_device_survivors(concat, None, dev_idx, cnt)
    r_cpu = compact_blocks(runs, CompactOptions(backend="cpu", now=100,
                                                bottommost=True,
                                                runs_sorted=True))
    np.testing.assert_array_equal(r_cpu.block.key_arena, out.key_arena)
    np.testing.assert_array_equal(r_cpu.block.val_arena, out.val_arena)


def test_cached_value_residency_matches_cpu():
    """Cached runs with pinned value rows (pack_run_device with_values):
    compact_blocks takes the device-materialization branch and stays
    byte-identical to the cpu lane; mixed caches (one run without values)
    fall back to the host gather, same bytes."""
    from pegasus_tpu.ops.compact import pack_run_device

    rng = np.random.default_rng(31)
    runs = _uniform_runs(rng, n_runs=3, n=350)
    opts = dict(now=100, bottommost=True, runs_sorted=True)
    cpu = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    drs_v = [pack_run_device(b, with_values=True) for b in runs]
    assert all(d is not None and d.val2d is not None for d in drs_v)
    got = compact_blocks(runs, CompactOptions(backend="tpu", **opts),
                         device_runs=drs_v)
    # mixed: one run lacks values -> host-gather fallback branch
    drs_mixed = [pack_run_device(runs[0])] + drs_v[1:]
    mixed = compact_blocks(runs, CompactOptions(backend="tpu", **opts),
                           device_runs=drs_mixed)
    for other in (got, mixed):
        assert other.block.n == cpu.block.n
        np.testing.assert_array_equal(cpu.block.key_arena, other.block.key_arena)
        np.testing.assert_array_equal(cpu.block.val_arena, other.block.val_arena)
        np.testing.assert_array_equal(cpu.block.expire_ts, other.block.expire_ts)
        np.testing.assert_array_equal(cpu.block.deleted, other.block.deleted)


def test_engine_device_values_end_to_end(tmp_path):
    """EngineOptions.device_values=True: uniform-width tables compact
    through the value-residency branch and serve identical data to cpu."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import SCHEMAS
    from pegasus_tpu.engine import EngineOptions, LsmEngine

    engines = {}
    for backend, dv in (("cpu", False), ("tpu", True)):
        eng = LsmEngine(str(tmp_path / backend), EngineOptions(
            backend=backend, memtable_bytes=8 << 10,
            l0_compaction_trigger=3, device_values=dv))
        for i in range(500):
            key = generate_key(b"h%03d" % (i % 41), b"s%05d" % i)
            eng.put(key, SCHEMAS[2].generate_value(0, 0, b"pay%07d" % i))
        eng.manual_compact(now=100)
        engines[backend] = eng
    tpu = engines["tpu"]
    primed = [s for s in tpu._l0 + sum(tpu._levels.values(), [])
              if s._device_run is not None and s._device_run.val2d is not None]
    assert primed, "no SST holds resident value rows"
    for i in range(500):
        key = generate_key(b"h%03d" % (i % 41), b"s%05d" % i)
        assert engines["cpu"].get(key) == tpu.get(key), f"diverged at {i}"
    for eng in engines.values():
        eng.close()


def test_intra_run_duplicate_keys_byte_equal_and_correct():
    """r5 regression (seed 11): runs with DUPLICATE keys inside one run —
    legal for raw external sets, never produced by the engine — must
    compact byte-equal across backends and match the model (newest run
    wins; within a run the FIRST occurrence wins). The device merge
    networks are not stable, so pack_runs now first-wins-dedups any run
    it host-sorts, and merge_body keys the sort on original position."""
    rng = np.random.default_rng(11)
    runs = [make_block(_adversarial_records(rng, 350)) for _ in range(3)]

    merged = {}
    for b in runs:  # newest first
        seen = set()
        for i in range(b.n):
            k = b.key(i)
            if k in seen:
                continue
            seen.add(k)
            if k not in merged:
                merged[k] = (b.value(i), int(b.expire_ts[i]),
                             bool(b.deleted[i]))
    now = 60
    want = {(k, v) for k, (v, e, d) in merged.items()
            if not d and not (0 < e <= now)}

    cpu = compact_blocks(runs, CompactOptions(backend="cpu", now=now,
                                              bottommost=True,
                                              runs_sorted=None))
    tpu = compact_blocks(runs, CompactOptions(backend="tpu", now=now,
                                              bottommost=True,
                                              runs_sorted=None))
    got_cpu = {(cpu.block.key(i), cpu.block.value(i))
               for i in range(cpu.block.n)}
    assert got_cpu == want
    assert bytes(cpu.block.key_arena) == bytes(tpu.block.key_arena)
    assert bytes(cpu.block.val_arena) == bytes(tpu.block.val_arena)


def test_sorted_dup_runs_backend_parity_and_stats():
    """r5 review findings: (1) a PRE-SORTED run carrying duplicate keys
    (runs_sorted=True skips only the sort check, not uniqueness) must
    dedup identically on both backends; (2) stats count RAW input rows on
    every path, not post-dedup pack lengths."""
    recs = []
    for i in range(50):
        recs.append((b"hk%02d" % (i % 10), b"s%03d" % i, b"v%d" % i, 0, False))
        if i % 5 == 0:  # duplicate key, older value — must be shadowed
            recs.append((b"hk%02d" % (i % 10), b"s%03d" % i, b"OLD", 0, False))
    blocks = [make_block(sorted(recs, key=lambda r: (len(r[0]), r[0], r[1])))]
    # make_block sorts? ensure sortedness by building then asserting
    b = blocks[0]
    keys = [b.key(i) for i in range(b.n)]
    assert keys == sorted(keys)
    raw_n = b.n
    cpu = compact_blocks([b], CompactOptions(backend="cpu", now=5,
                                             runs_sorted=True))
    tpu = compact_blocks([b], CompactOptions(backend="tpu", now=5,
                                             runs_sorted=True))
    assert bytes(cpu.block.key_arena) == bytes(tpu.block.key_arena)
    assert bytes(cpu.block.val_arena) == bytes(tpu.block.val_arena)
    assert b"OLD" not in bytes(cpu.block.val_arena)  # first-wins kept new
    assert cpu.stats["input_records"] == tpu.stats["input_records"] == raw_n


# ------------------------------------------- gather by run, no concat (PR 28)


def _fill_like_runs(rng, n_runs, n):
    """Uniform-width runs shaped like the bulk fill: few older versions,
    little TTL, tombstones that keep the value width, so most rows
    survive (the chunked index download starts at 65,536 survivors)."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import SCHEMAS

    runs = []
    for _ in range(n_runs):
        rows = {}
        for hk in rng.integers(0, 10 * n, size=n):
            expire = int(rng.integers(1, 90)) if rng.random() < 0.1 else 0
            key = generate_key(b"h%08d" % hk, b"s%03d" % rng.integers(0, 4))
            rows[key] = (key, SCHEMAS[2].generate_value(
                expire, 0, b"p%09d" % rng.integers(0, 10**9)), expire,
                bool(rng.random() < 0.05))
        runs.append(sort_block(KVBlock.from_records(rows.values())))
    return runs


def _assert_blocks_equal(want: KVBlock, got: KVBlock):
    for f in ("key_arena", "key_off", "key_len", "val_arena", "val_off",
              "val_len", "expire_ts", "hash32", "deleted"):
        np.testing.assert_array_equal(getattr(want, f), getattr(got, f),
                                      err_msg=f)


def _gather_counts():
    from pegasus_tpu.runtime.perf_counters import counters

    return (counters.number("compact.gather.by_run_count").value(),
            counters.number("compact.gather.concat_count").value())


@pytest.mark.parametrize("n", [400, 24000], ids=["one_chunk", "chunked"])
def test_uniform_runs_gather_by_run_without_concat(n):
    """Four uniform runs: the tpu lane (host-packed and over cached device
    runs) and the cpu lane gather by run — byte-equal to each other and to
    the construction they replaced (KVBlock.concat + gather by the same
    survivors), no `concat` stage in the trace, by_run_count moved."""
    from pegasus_tpu.ops.compact import (get_backend, pack_run_device,
                                         pack_runs)
    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    rng = np.random.default_rng(n)
    runs = _fill_like_runs(rng, 4, n)
    assert len({r.uniform_layout() for r in runs}) == 1
    opts = dict(now=100, bottommost=True, runs_sorted=True)
    packed = pack_runs(runs, CompactOptions(backend="cpu", **opts),
                       need_sbytes=True)
    survivors = get_backend("cpu").survivors(packed, 100, 0, 0, True, True)
    want = KVBlock.concat(runs).gather(survivors)
    if n > 400:
        assert want.n >= 1 << 16   # the chunked index download's floor

    lanes = {"cpu": dict(backend="cpu"), "tpu": dict(backend="tpu"),
             "tpu_cached": dict(backend="tpu")}
    for lane, kw in lanes.items():
        device_runs = ([pack_run_device(b) for b in runs]
                       if lane == "tpu_cached" else None)
        by_run0, concat0 = _gather_counts()
        with COMPACT_TRACER.session() as sess:
            got = compact_blocks(runs, CompactOptions(**kw, **opts),
                                 device_runs=device_runs)
        _assert_blocks_equal(want, got.block)
        assert "gather" in sess.stages, (lane, sess.stages)
        assert "concat" not in sess.stages, (lane, sess.stages)
        by_run1, concat1 = _gather_counts()
        assert (by_run1 - by_run0, concat1 - concat0) == (1, 0), lane


def test_variable_width_runs_still_concat_then_gather():
    """Variable-width keys and values (the redis proxy's, geo's) cannot be
    indexed by arithmetic: the `concat` stage closes, concat_count moves,
    by_run_count does not, and both lanes still agree byte for byte."""
    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    rng = np.random.default_rng(17)
    runs = [sort_block(make_block(_adversarial_records(rng, 150)))
            for _ in range(4)]
    assert any(r.uniform_layout() is None for r in runs)
    opts = dict(now=100, bottommost=True, runs_sorted=True)
    outs = {}
    for backend in ("cpu", "tpu"):
        by_run0, concat0 = _gather_counts()
        with COMPACT_TRACER.session() as sess:
            outs[backend] = compact_blocks(
                runs, CompactOptions(backend=backend, **opts)).block
        assert "concat" in sess.stages and "gather" in sess.stages
        by_run1, concat1 = _gather_counts()
        assert (by_run1 - by_run0, concat1 - concat0) == (0, 1), backend
    _assert_blocks_equal(outs["cpu"], outs["tpu"])


def test_mixed_width_uniform_runs_fall_back_to_concat():
    """Every run uniform, but not of ONE width: still the fallback."""
    rng = np.random.default_rng(23)
    a = _uniform_runs(rng, n_runs=1, n=200)[0]
    rows = [(a.key(i) + b"x", a.value(i), int(a.expire_ts[i]),
             bool(a.deleted[i])) for i in range(a.n)]
    b = sort_block(KVBlock.from_records(rows))
    assert a.uniform_layout() and b.uniform_layout()
    assert a.uniform_layout() != b.uniform_layout()
    opts = dict(now=100, bottommost=True, runs_sorted=True)
    by_run0, concat0 = _gather_counts()
    cpu = compact_blocks([a, b], CompactOptions(backend="cpu", **opts))
    tpu = compact_blocks([a, b], CompactOptions(backend="tpu", **opts))
    by_run1, concat1 = _gather_counts()
    assert (by_run1 - by_run0, concat1 - concat0) == (0, 2)
    _assert_blocks_equal(cpu.block, tpu.block)
