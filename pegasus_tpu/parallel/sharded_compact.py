"""Multi-chip compaction: hash-sharded sort/merge with an all_to_all exchange.

The TPU-native answer to "compaction of a multi-GB partition is bigger than
one chip" (SURVEY.md §5.7c): records are hash-classed by key (`hash32 %
n_shards` — every version of a key, and every sort_key of a hash_key, lands
in the same class), each chip takes one class, and a single all_to_all over
the mesh's ICI routes records from whichever input run they arrived in to
their owning chip. Each chip then runs the same merge_body as the
single-chip kernel on its class. SPMD via shard_map; no NCCL/MPI analogue —
the exchange is an XLA collective.

Output is a list of per-shard KVBlocks: independent sorted runs over
disjoint hash classes (the sharded-SST layout). Their union equals the
single-chip compaction output exactly.

Routing uses fixed per-(src,dst) capacity `cap` (static shapes for XLA);
rows past capacity are counted, and the host retries with full capacity on
overflow — hash uniformity makes that rare at sane capacity factors.
"""

import functools
from dataclasses import replace

import numpy as np

from ..engine.block import KVBlock
from ..ops.compact import (CompactOptions, CompactResult, _apply_default_ttl,
                           _pow2ceil, _stats, apply_post_filters, merge_body,
                           sort_block)
from ..ops.kernel import DeviceKernel
from ..ops.packing import (compute_suffix_ranks, pack_key_prefixes,
                           window_lanes)
from ..runtime.fail_points import inject as _inject
from ..runtime.lane_guard import LANE_GUARD
from ..runtime.tracing import COMPACT_TRACER as _TRACE


def _next_bucket(n: int) -> int:
    return _pow2ceil(n, 1024)


@functools.lru_cache(maxsize=32)
def _sharded_kernel(mesh_key, w: int, n_loc: int, cap: int, axis: str):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _MESHES[mesh_key]
    nsh = mesh.shape[axis]
    nrecv = nsh * cap

    def per_device(cols, rank, klen, prio, expire, deleted, hash32, valid, gid,
                   now, pidx, pmask, bottommost, do_filter):
        # local slice: cols [w, n_loc], rest [n_loc]
        dest = (hash32 % jnp.uint32(nsh)).astype(jnp.int32)
        order = jnp.argsort(dest)
        dest_s = dest[order]
        counts = jnp.bincount(dest, length=nsh).astype(jnp.int32)
        starts = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
        )
        within = jnp.arange(n_loc, dtype=jnp.int32) - starts[dest_s]
        ok = (within < cap) & valid[order]
        slot = jnp.where(ok, dest_s * cap + within, nrecv)  # nrecv = OOB drop
        overflow = jnp.sum((within >= cap) & valid[order]).astype(jnp.int32)

        def route(x, fill):
            buf = jnp.full((nrecv,), fill, dtype=x.dtype)
            buf = buf.at[slot].set(x[order], mode="drop")
            return lax.all_to_all(
                buf.reshape(nsh, cap), axis, split_axis=0, concat_axis=0
            ).reshape(nrecv)

        r_cols = [route(cols[i], jnp.uint32(0)) for i in range(w)]
        r_rank = route(rank, jnp.uint32(0))
        r_klen = route(klen, jnp.uint32(0))
        r_prio = route(prio, jnp.uint32(0))
        r_expire = route(expire, jnp.uint32(0))
        r_deleted = route(deleted, jnp.bool_(False))
        r_hash = route(hash32, jnp.uint32(0))
        r_valid = route(valid, jnp.bool_(False))
        r_gid = route(gid, jnp.int32(-1))

        perm, keep = merge_body(
            r_cols, r_rank, r_klen, r_prio, r_expire, r_deleted, r_hash, r_valid,
            now, pidx, pmask, bottommost, do_filter,
            # the routing scrambled row order: tie-break intra-run
            # duplicate keys by ORIGINAL concat position, matching the
            # host backend's stable first-wins (invalid rows carry gid -1
            # but every sort key is already forced to the max there)
            pos=r_gid.astype(jnp.uint32),
        )
        return r_gid[perm], keep, overflow[None]

    smap = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(None, axis), P(axis), P(axis), P(axis), P(axis), P(axis),
            P(axis), P(axis), P(axis), P(), P(), P(), P(), P(),
        ),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    return DeviceKernel(smap, "merge_sharded")


# shard_map needs the concrete Mesh at trace time; lru_cache keys must be
# hashable, so meshes are interned here by id-key
_MESHES = {}


def _intern_mesh(mesh):
    key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
    _MESHES[key] = mesh
    return key


def sharded_compact(blocks, mesh, opts: CompactOptions, axis: str = "shard",
                    capacity_factor: float = 2.0):
    """Compact K runs (newest first) across the mesh. Returns
    (list[KVBlock] per shard, stats dict)."""
    import jax.numpy as jnp

    runs = [b for b in blocks if b.n]
    nsh = mesh.shape[axis]
    if not runs:
        return [KVBlock.empty() for _ in range(nsh)], {"input_records": 0,
                                                       "output_records": 0, "dropped": 0}
    block = runs[0] if len(runs) == 1 else KVBlock.concat(runs)
    prio = np.repeat(np.arange(len(runs), dtype=np.uint32), [b.n for b in runs])
    n = block.n
    # as wide as the longest key needs, up to the cap (ops/packing.py)
    w = window_lanes(int(block.key_len.max()), opts.prefix_u32)
    n_loc = _next_bucket(-(-n // nsh))
    n_pad = n_loc * nsh

    with _TRACE.span("pack", records=n):
        prefixes = pack_key_prefixes(block.key_arena, block.key_off,
                                     block.key_len, w)
        rank = compute_suffix_ranks(block, w, prefixes)

        def pad(a, fill=0):
            out = np.full(n_pad, fill, dtype=a.dtype)
            out[:n] = a
            return out

        cols = np.zeros((w, n_pad), np.uint32)
        cols[:, :n] = prefixes.T
        args = (
            pad(rank), pad(block.key_len.astype(np.uint32)), pad(prio),
            pad(block.expire_ts), pad(block.deleted), pad(block.hash32),
            pad(np.ones(n, dtype=bool), False),
            pad(np.arange(n, dtype=np.int32), -1),
        )
    now = opts.resolved_now()
    scalars = (jnp.uint32(now), jnp.uint32(opts.pidx), jnp.uint32(opts.partition_mask),
               jnp.asarray(bool(opts.bottommost)), jnp.asarray(bool(opts.filter)))

    mesh_key = _intern_mesh(mesh)
    # pow2 capacity so nrecv = nsh*cap is pow2 -> the merge takes the bitonic
    # path (nsh is a pow2 device count)
    def pow2ceil(x):
        p = 1
        while p < x:
            p <<= 1
        return p

    cap = min(n_loc, max(8, pow2ceil(int(n_loc / nsh * capacity_factor))))
    # the kernel span covers upload + all_to_all + merge + download (the
    # np.asarray calls sync); a capacity-overflow retry re-enters the span
    while True:
        with _TRACE.span("device", records=n):
            _inject("compact.device")
            fn = _sharded_kernel(mesh_key, w, n_loc, cap, axis)
            gid_sorted, keep, overflow = fn(cols, *args, *scalars)
            # which chips hold the output shards (stats: a mesh that
            # quietly collapsed onto one device shows up here)
            device_ids = sorted(s.device.id
                                for s in gid_sorted.addressable_shards)
            gid_sorted = np.asarray(gid_sorted)
            keep = np.asarray(keep)
        if int(np.asarray(overflow).sum()) == 0:
            break
        if cap >= n_loc:  # can't happen: full capacity admits every row
            raise RuntimeError("sharded_compact overflow at full capacity")
        cap = n_loc  # retry with loss-proof capacity

    nrecv = nsh * cap
    shards = []
    out_total = 0
    with _TRACE.span("gather") as sp:
        for s in range(nsh):
            seg_ids = gid_sorted[s * nrecv : (s + 1) * nrecv]
            seg_keep = keep[s * nrecv : (s + 1) * nrecv]
            ids = seg_ids[seg_keep]
            shard = block.gather(ids)
            if opts.filter and opts.default_ttl > 0:
                _apply_default_ttl(shard, now + opts.default_ttl)
            out_total += shard.n
            shards.append(shard)
        sp["records"] = out_total
    return shards, {"input_records": n, "output_records": out_total,
                    "dropped": n - out_total, "n_shards": nsh, "capacity": cap,
                    "device_ids": device_ids}


def sharded_compact_block(blocks, mesh, opts: CompactOptions,
                          axis: str = "shard") -> CompactResult:
    """Engine seam (VERDICT-r3 item 7): run the multi-chip hash-sharded
    compaction and reassemble ONE key-sorted block byte-equal to
    `compact_blocks(blocks, opts)` — what LsmEngine.manual_compact installs
    when its mesh has >1 device (the reference's analogue spreads
    partition-ranged compaction work across nodes; here the spread is
    hash classes across chips and the final order is restored on install).

    Equality argument: hash-classing sends every version of a key to one
    shard, each shard's merge_body output is key-sorted and deduped, so
    shard outputs hold DISJOINT key sets whose union is exactly the
    single-chip survivor set. A stable key sort of their concatenation is
    therefore the single-chip output order. Post filters (user compaction
    rules, default-TTL rewrite) run after reassembly in compact_blocks'
    exact order — the kernel runs with them masked off."""
    # resolve `now` ONCE: the kernel's TTL drops and the post filters must
    # agree on the clock or the output can differ from the single-chip
    # result for records expiring between two resolved_now() calls
    opts = replace(opts, now=opts.resolved_now())

    def _device_lane() -> CompactResult:
        kernel_opts = replace(opts, default_ttl=0, user_ops=())
        shards, stats = sharded_compact(blocks, mesh, kernel_opts, axis=axis)
        live = [s for s in shards if s.n]
        if not live:
            return CompactResult(KVBlock.empty(),
                                 _stats(stats["input_records"], 0))
        merged = live[0] if len(live) == 1 else KVBlock.concat(live)
        out = sort_block(merged, CompactOptions(prefix_u32=opts.prefix_u32,
                                                backend=opts.backend))
        out = apply_post_filters(out, opts, opts.now)
        return CompactResult(out, _stats(stats["input_records"], out.n))

    def _cpu_lane() -> CompactResult:
        from ..ops.compact import compact_blocks

        return compact_blocks(blocks, replace(opts, backend="cpu"))

    # the lane guard makes the multi-chip path safe to prefer: a wedged
    # collective / dead chip degrades to the single-node cpu merge, whose
    # output this function is byte-equal to by construction
    return LANE_GUARD.run(_device_lane, _cpu_lane, op="sharded_compact")


def compact_blocks_meshed(blocks, opts: CompactOptions,
                          mesh=None) -> CompactResult:
    """Merge entry for the compaction-offload service (ISSUE 14): one
    call that multiplexes tenants across whatever the host owns — the
    all_to_all hash-sharded kernel when the mesh spans >1 device, the
    guarded single-chip merge for a device backend, the plain host merge
    otherwise. Every path is byte-equal to ``compact_blocks(blocks,
    opts)`` on cpu (the sharded path by sharded_compact_block's
    reassembly argument, the single-chip path by the standing
    device-vs-host contract), so a cpu-only tenant's local fallback and
    the service's merged output can never diverge."""
    from ..ops.compact import compact_blocks

    if mesh is not None and mesh.devices.size > 1:
        return sharded_compact_block(blocks, mesh, opts)
    if opts.backend != "cpu":
        return LANE_GUARD.run(
            lambda: compact_blocks(blocks, opts),
            lambda: compact_blocks(blocks, replace(opts, backend="cpu")),
            op="offload_merge")
    return compact_blocks(blocks, opts)
