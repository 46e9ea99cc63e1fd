"""Batched multi-partition compaction: many merges in ONE device dispatch.

A replica node hosts many partitions whose compactions are independent —
the reference runs them as separate RocksDB CompactRange jobs on a thread
pool (src/server/pegasus_server_impl.cpp manual-compact concurrency knob).
The TPU-native shape is different: vmap the cached-run merge pipeline over
a leading partition axis, so B same-bucket-shape partition compactions
cost ONE kernel launch (amortizing per-dispatch overhead — tens of µs
on a local host) and fill the chip at small per-partition sizes.

Across a multi-chip `jax.sharding.Mesh` the batch axis shards over
devices (dp that MATCHES the partition→replica layout: each chip owns
whole partitions, no cross-chip exchange at all) — the complementary
strategy to parallel.sharded_compact's all_to_all hash routing, which
splits ONE oversized merge across chips.

Partitions are grouped by their shape signature (padded bucket lengths ×
run widths × w); each group is one dispatch. Within a group the per-run
device columns stack on axis 0 (HBM-to-HBM copies; the PCIe upload
already happened when the runs' DeviceRuns were born).
"""

import functools

import numpy as np

from ..runtime.fail_points import inject as _inject
from ..runtime.lane_guard import LANE_GUARD
from ..runtime.tracing import COMPACT_TRACER as _TRACE
from .compact import (CompactOptions, _make_cached_fn, apply_post_filters,
                      gather_runs)
from .kernel import DeviceKernel


@functools.lru_cache(maxsize=128)
def _compiled_batched_pipeline(padded_lens: tuple, run_ws: tuple, w: int):
    """vmap(cached pipeline) as one DeviceKernel: leading axis = partition. Per-partition
    variation rides as batched args (real run lengths, pidx); table-wide
    knobs broadcast. Pallas is disabled under vmap (pallas_call batching
    is not wired up); the merge networks vmap natively."""
    import jax

    fn = _make_cached_fn(padded_lens, run_ws, w, allow_pallas=False)
    return DeviceKernel(
        jax.vmap(fn, in_axes=(0, 0, 0, None, 0, None, None, None)),
        "merge_batched")


def _signature(device_runs):
    return (tuple(r.padded_len for r in device_runs),
            tuple(r.w for r in device_runs),
            max(r.w for r in device_runs))


def _stack_group(jobs):
    """jobs: list of (device_runs, pidx). -> vmapped arg tuple."""
    import jax.numpy as jnp

    K = len(jobs[0][0])
    cached = tuple(
        tuple(jnp.stack([job[0][i].cols[j] for job in jobs])
              for j in range(jobs[0][0][i].w))
        + (jnp.stack([job[0][i].klen for job in jobs]),)
        for i in range(K))
    aux = tuple(
        (jnp.stack([job[0][i].expire for job in jobs]),
         jnp.stack([job[0][i].deleted for job in jobs]),
         jnp.stack([job[0][i].hash32 for job in jobs]))
        for i in range(K))
    real_lens = jnp.asarray([[r.n for r in job[0]] for job in jobs],
                            jnp.int32)
    pidx = jnp.asarray([job[1] for job in jobs], jnp.uint32)
    return cached, aux, real_lens, pidx


def compact_partition_batch(jobs, opts: CompactOptions, mesh=None,
                            post_opts=None):
    """jobs: list of (runs: [KVBlock], device_runs: [DeviceRun], pidx).
    Every job's runs must be sorted and fully device-cached; all jobs in
    one call may have ANY shapes — they are grouped by signature here,
    one dispatch per group. -> list of output KVBlocks (job order).

    mesh: optional jax.sharding.Mesh. Groups whose job count is a
    MULTIPLE of the mesh size shard the batch axis across devices (pure
    dp: each chip compacts its partitions with zero collectives); other
    groups run single-device.

    post_opts: optional per-job CompactOptions for the HOST post passes
    (user rules, default_ttl) when jobs carry different app envs; the
    in-dispatch knobs (partition_mask, bottommost, filter) still come
    from `opts` and broadcast — callers must group jobs accordingly.

    Semantically identical to per-job compact_blocks(runs, opts,
    device_runs) with opts.pidx = job pidx — including the user-rule and
    default-TTL post passes (byte-equal; test-enforced). Groups chunk so
    one dispatch never stacks more than opts.max_device_records rows; a
    SINGLE job beyond that budget routes through compact_blocks, whose
    blockwise path range-decomposes it instead of OOMing one dispatch.

    Chunks pipeline (ops/pipeline.py): the next chunk's host stacking
    prefetches on a pool worker under the current chunk's device
    dispatch, bounded by PEGASUS_COMPACT_PIPELINE_DEPTH.
    """
    from .compact import compact_blocks
    from .pipeline import CompactPipeline

    now = opts.resolved_now()
    outs = [None] * len(jobs)
    groups = {}
    for j, (runs, device_runs, pidx) in enumerate(jobs):
        if not runs or any(d is None for d in device_runs):
            raise ValueError(f"job {j}: all runs must be device-cached")
        if sum(d.padded_len for d in device_runs) > opts.max_device_records:
            from dataclasses import replace

            job_opts = replace(post_opts[j] if post_opts else opts,
                               pidx=pidx, backend="tpu", runs_sorted=True)
            outs[j] = compact_blocks(runs, job_opts,
                                     device_runs=device_runs).block
            continue
        groups.setdefault(_signature(device_runs), []).append(j)
    chunks = []
    for sig, all_idxs in groups.items():
        padded_lens, run_ws, w = sig
        # device budget: one dispatch stacks B x sum(padded_lens) rows —
        # chunk the group rather than OOM HBM (compact_blocks' blockwise
        # guard, adapted to the batch axis)
        per_job = sum(padded_lens)
        max_b = max(1, int(opts.max_device_records // max(1, per_job)))
        if mesh is not None and max_b >= mesh.size:
            # keep chunks mesh-divisible, or the dp sharding silently
            # disengages for every chunk
            max_b -= max_b % mesh.size
        for chunk_at in range(0, len(all_idxs), max_b):
            chunks.append((sig, all_idxs[chunk_at:chunk_at + max_b]))

    def _prefetch(chunk):
        sig, idxs = chunk
        if LANE_GUARD.breaker_open(probe=False):
            # the guard will route this chunk straight to cpu — poking a
            # device the breaker has declared dead from an unguarded
            # worker would only wedge pool workers for nothing
            return RuntimeError("breaker open: prefetch skipped")
        try:
            return _stack_and_place(jobs, idxs, sig, mesh)
        except Exception as e:  # noqa: BLE001 - the guarded dispatch
            # re-stacks inline, so a stacking failure (device error, armed
            # fail point) flows into the lane guard's retry/fallback
            # policy instead of aborting the whole batch
            return e

    def _dispatch(i, prestacked):
        sig, idxs = chunks[i]
        if isinstance(prestacked, Exception):
            prestacked = None
        _run_group(jobs, idxs, sig, opts, now, mesh, outs, post_opts,
                   prestacked=prestacked)

    # this map runs OUTSIDE any lane guard (each chunk's _run_group has
    # its own), so prefetch pickup must be bounded: a wedged stacking
    # worker is abandoned at the lane deadline and the chunk re-stacks
    # inline under its guard — deadline/fallback/breaker all still apply.
    # deadline <= 0 means "deadline disabled": wait unbounded like the
    # guard would, never insta-timeout every prefetch
    eff = LANE_GUARD.effective_deadline_s()
    CompactPipeline(
        prefetch_timeout_s=(eff if eff and eff > 0 else None)
    ).map(chunks, _prefetch, _dispatch)
    return outs


def _stack_and_place(jobs, idxs, sig, mesh):
    """The chunk's "h2d" stage: stack the group's cached runs on the batch
    axis (+ the dp re-placement) — HBM-to-HBM copies (the PCIe upload
    already happened when the DeviceRuns were born), prefetchable on a
    pipeline worker under the previous chunk's device dispatch."""
    import jax

    padded_lens, _, _ = sig
    with _TRACE.span("h2d", records=len(idxs) * sum(padded_lens)):
        _inject("compact.h2d")
        cached, aux, real_lens, pidx_arr = _stack_group(
            [(jobs[j][1], jobs[j][2]) for j in idxs])
        if mesh is not None and len(idxs) % mesh.size == 0:
            from jax.sharding import NamedSharding, PartitionSpec

            axis = mesh.axis_names[0]

            def shard_batch(x):
                spec = PartitionSpec(axis, *([None] * (x.ndim - 1)))
                return jax.device_put(x, NamedSharding(mesh, spec))

            cached = jax.tree_util.tree_map(shard_batch, cached)
            aux = jax.tree_util.tree_map(shard_batch, aux)
            real_lens = shard_batch(real_lens)
            pidx_arr = shard_batch(pidx_arr)
    return cached, aux, real_lens, pidx_arr


def _run_group(jobs, idxs, sig, opts, now, mesh, outs, post_opts=None,
               prestacked=None):
    """One dispatch: stack the group's cached runs (or consume the
    pipeline's prefetched stack), run jit(vmap), gather + post-filter
    each row's survivors into outs[job]. The whole dispatch runs under
    the lane guard: a wedge/failure falls back to per-job cpu
    compactions (byte-identical by contract)."""

    def _device_group() -> dict:
        nonlocal prestacked
        import jax.numpy as jnp

        from ..engine.block import KVBlock

        padded_lens, run_ws, w = sig
        fn = _compiled_batched_pipeline(padded_lens, run_ws, w)
        if prestacked is not None:
            cached, aux, real_lens, pidx_arr = prestacked
            prestacked = None  # a retry re-stacks: the stack may be the fault
        else:
            cached, aux, real_lens, pidx_arr = _stack_and_place(
                jobs, idxs, sig, mesh)
        # np.asarray(counts) syncs on the whole batched dispatch
        with _TRACE.span("device", records=len(idxs) * sum(padded_lens)):
            _inject("compact.device")
            out_idx, counts = fn(cached, aux, real_lens, jnp.uint32(now),
                                 pidx_arr, jnp.uint32(opts.partition_mask),
                                 jnp.asarray(bool(opts.bottommost)),
                                 jnp.asarray(bool(opts.filter)))
            counts = np.asarray(counts)
        group_outs = {}
        for row, j in enumerate(idxs):
            runs = jobs[j][0]
            concat = runs[0] if len(runs) == 1 else KVBlock.concat(runs)
            out = gather_runs([concat], out_idx[row], int(counts[row]))
            group_outs[j] = apply_post_filters(
                out, post_opts[j] if post_opts else opts, now)
        return group_outs

    def _cpu_group() -> dict:
        from dataclasses import replace

        from .compact import compact_blocks

        group_outs = {}
        for j in idxs:
            runs, _, pidx = jobs[j]
            job_opts = replace(
                post_opts[j] if post_opts else opts,
                pidx=pidx, backend="cpu", runs_sorted=True, now=now,
                partition_mask=opts.partition_mask,
                bottommost=opts.bottommost, filter=opts.filter)
            group_outs[j] = compact_blocks(runs, job_opts).block
        return group_outs

    results = LANE_GUARD.run(_device_group, _cpu_group, op="batched_compact")
    for j, block in results.items():
        outs[j] = block
