"""Batched multi-partition compaction: one dispatch, many partitions.

Differential contract: compact_partition_batch must be byte-equal to
per-partition compact_blocks over cached device runs, for mixed shapes
(grouped dispatches), per-partition split GC masks, and when the batch
axis shards across a multi-device mesh (the dp-over-partitions story).
"""

import numpy as np
import pytest

from pegasus_tpu.ops.batched_compact import (_compiled_batched_pipeline,
                                             compact_partition_batch)
from pegasus_tpu.ops.compact import (CompactOptions, compact_blocks,
                                     pack_run_device, sort_block)
from tests.test_compact_ops import make_block


def make_partition(seed, n, hk_space=120, k_runs=2):
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        hk = b"p%05d" % rng.integers(0, hk_space)
        deleted = bool(rng.random() < 0.1)
        expire = int(rng.integers(0, 3)) * 40
        recs.append((hk, b"s%d" % (i % 4), b"" if deleted else b"v%d" % i,
                     expire, deleted))
    per = n // k_runs
    runs = [sort_block(make_block(recs[i * per:(i + 1) * per]),
                       CompactOptions(backend="cpu"))
            for i in range(k_runs)]
    device_runs = [pack_run_device(b) for b in runs]
    assert all(d is not None for d in device_runs)
    return runs, device_runs


@pytest.mark.parametrize("mesh_dp", [False, True])
def test_batched_matches_per_partition(mesh_dp):
    opts = CompactOptions(backend="tpu", now=60, bottommost=True,
                          runs_sorted=True)
    # 8 partitions: 6 share one shape signature, 2 are a different size
    jobs = []
    for pidx in range(6):
        runs, drs = make_partition(100 + pidx, 400)
        jobs.append((runs, drs, pidx))
    for pidx in (6, 7):
        runs, drs = make_partition(100 + pidx, 700)
        jobs.append((runs, drs, pidx))
    mesh = None
    if mesh_dp:
        import jax
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[:2])
        # the suite's conftest forces an 8-virtual-device CPU platform;
        # fail LOUDLY if that regresses — a size-1 mesh would make this
        # parametrization silently identical to mesh_dp=False
        assert len(devs) == 2, "need >=2 devices for the dp sharding test"
        mesh = Mesh(devs, ("dp",))
    outs = compact_partition_batch(jobs, opts, mesh=mesh)
    for (runs, drs, pidx), got in zip(jobs, outs):
        from dataclasses import replace

        want = compact_blocks(runs, replace(opts, pidx=pidx),
                              device_runs=drs)
        assert got.n == want.block.n
        np.testing.assert_array_equal(want.block.key_arena, got.key_arena)
        np.testing.assert_array_equal(want.block.val_arena, got.val_arena)
        np.testing.assert_array_equal(want.block.expire_ts, got.expire_ts)


def test_batched_per_partition_split_gc_mask():
    """pidx is a BATCHED argument: with a partition mask set, each row
    must drop exactly the keys its own partition no longer owns."""
    opts = CompactOptions(backend="tpu", now=60, bottommost=True,
                          runs_sorted=True, partition_mask=1)
    jobs = []
    for pidx in (0, 1):
        runs, drs = make_partition(7, 400)  # same seed: identical data
        jobs.append((runs, drs, pidx))
    outs = compact_partition_batch(jobs, opts)
    from dataclasses import replace

    for (runs, drs, pidx), got in zip(jobs, outs):
        want = compact_blocks(runs, replace(opts, pidx=pidx),
                              device_runs=drs)
        assert got.n == want.block.n
        np.testing.assert_array_equal(want.block.key_arena, got.key_arena)
    # the two partitions kept complementary halves
    assert outs[0].n + outs[1].n > 0
    h0 = set(outs[0].hash32.tolist())
    h1 = set(outs[1].hash32.tolist())
    assert all(h & 1 == 0 for h in h0)
    assert all(h & 1 == 1 for h in h1)


def test_batched_groups_share_compiled_programs():
    """Same shape signature across calls -> one compile, reused."""
    _compiled_batched_pipeline.cache_clear()
    opts = CompactOptions(backend="tpu", now=60, runs_sorted=True)
    for seed in (1, 2, 3):
        jobs = []
        for pidx in range(3):
            # varying real sizes within one pow2 bucket
            runs, drs = make_partition(seed * 10 + pidx, 300 + 40 * pidx)
            jobs.append((runs, drs, pidx))
        compact_partition_batch(jobs, opts)
    info = _compiled_batched_pipeline.cache_info()
    # (a cold kernel's first call re-enters the builder once more after the
    # guard waited for its compile: one extra hit when nothing was cached)
    assert info.misses == 1 and info.hits in (2, 3), info


def test_batched_applies_user_rules_and_default_ttl():
    """The batched path must run the same post passes as compact_blocks
    (user compaction rules, table default_ttl) — byte-equal outputs."""
    from dataclasses import replace

    from pegasus_tpu.engine.compaction_rules import \
        parse_user_specified_compaction

    ops = tuple(parse_user_specified_compaction(
        '{"ops": [{"type": "COT_DELETE", "params": "{}", "rules": '
        '[{"type": "FRT_SORTKEY_PATTERN", "params": '
        '"{\\"pattern\\": \\"s1\\", \\"match_type\\": '
        '\\"SMT_MATCH_PREFIX\\"}"}]}]}'))
    assert ops
    opts = CompactOptions(backend="tpu", now=60, runs_sorted=True,
                          user_ops=ops, default_ttl=500)
    jobs = []
    for pidx in range(3):
        runs, drs = make_partition(60 + pidx, 300)
        jobs.append((runs, drs, pidx))
    outs = compact_partition_batch(jobs, opts)
    for (runs, drs, pidx), got in zip(jobs, outs):
        want = compact_blocks(runs, replace(opts, pidx=pidx),
                              device_runs=drs)
        assert got.n == want.block.n
        np.testing.assert_array_equal(want.block.key_arena, got.key_arena)
        np.testing.assert_array_equal(want.block.val_arena, got.val_arena)
        # the rules dropped the s1 sortkeys and default_ttl stamped expire
        from pegasus_tpu.base.key_schema import restore_key

        for i in range(got.n):
            assert not restore_key(got.key(i))[1].startswith(b"s1")
        assert (got.expire_ts[~got.deleted] > 0).all()


def test_batched_chunks_oversized_groups():
    """A group bigger than max_device_records splits into several
    dispatches instead of one giant stacked allocation."""
    from dataclasses import replace

    opts = CompactOptions(backend="tpu", now=60, runs_sorted=True,
                          max_device_records=1500)
    jobs = []
    for pidx in range(6):  # same signature; padded total/job = 1024
        runs, drs = make_partition(80 + pidx, 400)
        jobs.append((runs, drs, pidx))
    outs = compact_partition_batch(jobs, opts)
    for (runs, drs, pidx), got in zip(jobs, outs):
        want = compact_blocks(runs, replace(opts, pidx=pidx),
                              device_runs=drs)
        assert got.n == want.block.n
        np.testing.assert_array_equal(want.block.key_arena, got.key_arena)


def test_stub_batched_manual_compact(tmp_path):
    """Node-level batched manual compaction: a stub's tpu replicas compact
    in batched dispatches with the same results as per-replica
    manual_compact (digest-equal), updating the finish-time meta."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import SCHEMAS
    from pegasus_tpu.engine import EngineOptions
    from pegasus_tpu.engine.db import META_LAST_MANUAL_COMPACT_FINISH_TIME
    from pegasus_tpu.replication.replica import Replica

    def fill(rep, pidx):
        for i in range(300):
            rep.server.engine.put(
                generate_key(b"bm%d" % (i % 41), b"s%05d" % i),
                SCHEMAS[2].generate_value(0, 0, b"v%d.%d" % (pidx, i)))
            if i % 80 == 79:
                rep.server.engine.flush()

    import hashlib

    def digest(eng):
        h = hashlib.sha256()
        with eng._lock:
            files = list(eng._l0) + [f for lv in sorted(eng._levels)
                                     for f in eng._levels[lv]]
        for sst in files:
            b = sst.block()
            h.update(b.key_arena.tobytes())
            h.update(b.val_arena.tobytes())
        return h.hexdigest()

    # lane A: batched through a stub-shaped object
    class FakeStub:
        _lock = __import__("threading").RLock()

    from pegasus_tpu.replication.replica_stub import ReplicaStub

    stub = FakeStub()
    stub._replicas = {}
    reps = {}
    for pidx in range(4):
        rep = Replica(f"n0", str(tmp_path / f"b{pidx}"), app_id=1,
                      pidx=pidx, options=EngineOptions(backend="tpu"))
        fill(rep, pidx)
        stub._replicas[(1, pidx)] = rep
        reps[pidx] = rep
    stats = ReplicaStub.batched_manual_compact(stub, now=100)
    assert stats["batched"] == 4 and stats["fallback"] == 0
    assert stats["output_records"] > 0
    digests_batched = {p: digest(reps[p].server.engine) for p in reps}
    for rep in reps.values():
        assert META_LAST_MANUAL_COMPACT_FINISH_TIME in \
            rep.server.engine.meta_store
        rep.close()
    # lane B: plain per-replica manual_compact on identical data
    for pidx in range(4):
        rep = Replica(f"n1", str(tmp_path / f"s{pidx}"), app_id=1,
                      pidx=pidx, options=EngineOptions(backend="tpu"))
        fill(rep, pidx)
        rep.server.engine.manual_compact(now=100)
        assert digest(rep.server.engine) == digests_batched[pidx], pidx
        rep.close()
