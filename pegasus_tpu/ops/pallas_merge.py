"""Tier-2 merge kernel: merge-path chunking + whole-merge-in-VMEM Pallas.

The XLA networks in ops.device_sort materialize every compare-exchange
stage in HBM: a merge of length L costs ~log2(L) full passes (~24 at 16M).
This kernel cuts that to ~2 HBM passes: the classic GPU "merge path"
decomposition splits the output into fixed-size chunks along cross
diagonals of the merge matrix, and a Pallas program per chunk loads its
two input slices into VMEM, merges them entirely in VMEM, and writes its
finished output chunk once.

  1. diagonal search (plain jnp, outside the kernel): for each output
     position d = p*CHUNK, binary-search the split (ai, bi), ai+bi=d, such
     that A[ai-1] < B[bi] and B[bi-1] < A[ai] in the strict lexicographic
     column order (keys are unique by construction — the packed
     klen<<8|prio column differs across runs).
  2. pallas_call over grid=(P,): program p DMAs the TILE-ALIGNED windows
     A[al : al+W] and B[bl : bl+W] (al = ai rounded down to the 1024-lane
     VMEM tile, W = CHUNK + TILE) from HBM into VMEM scratch, merges the
     2W window bitonically, and stores rows [delta, delta+CHUNK) =
     out[d : d+CHUNK], where delta = d - al - bl.

Why aligned windows: Mosaic requires DMA slice offsets provably
divisible by the memref tiling (1024 elements for i32 1D); arbitrary
merge-path splits are not. Rounding both sides down to the tile keeps
every DMA offset aligned (asserted via pl.multiple_of) at the cost of
merging 2*(CHUNK+TILE) elements instead of 2*CHUNK. The residual
delta = (ai-al) + (bi-bl) is < 2*TILE and congruent to 0 mod 1024
(d is a multiple of CHUNK=2048; al, bl of 1024), so delta is always 0 or
1024 — a whole number of (8,128) rows, making the output window a select
between two static row slices. Correctness of the window trick: by the
merge-path property everything in A[:ai] ∪ B[:bi] strictly precedes
everything in A[ai:] ∪ B[bi:], so the sorted window's first delta
elements are exactly A[al:ai] ∪ B[bl:bi] and the next CHUNK are exactly
out[d : d+CHUNK] (the chunk consumes at most CHUNK from each side, which
the window covers).

Mosaic (real-TPU) lowering notes, learned on hardware:
  - refs in ANY/HBM space cannot be loaded directly; slices must move via
    pltpu.make_async_copy into VMEM scratch, with tile-aligned offsets.
  - per-program split offsets live in SMEM.
  - the in-VMEM merge runs on a 2D (rows, 128) layout: flat element k
    maps to (k // 128, k % 128). Stages with distance j >= 128 permute
    whole sublane rows (slice+concat along axis 0); stages with j < 128
    permute lanes via a 128x128 XOR one-hot matmul on the MXU (u32 split
    into u8 quarters, exact in bf16), built in-kernel from iotas (pallas
    forbids captured constant arrays).
  - no rev primitive (flat reversal = row-order concat + lane-reverse
    matmul); no select between i1 vectors (use boolean algebra); no
    uint32<->bfloat16 casts (route through int32/float32).

In interpret mode (CPU tests) the same windowed body runs with direct
ref loads instead of DMA — the generic interpreter does not model
Mosaic's memory spaces.

Gated by PEGASUS_PALLAS (default OFF; =1 enables). The TPU body lowers
through Mosaic on a v5e (jax 0.9.0, libtpu 0.0.34) and its output is
byte-equal to CpuBackend's at 1M and 10M records (chip runs of PR 21).
The default stays off: turning it on is a performance change, to be made
with paired measurements (ROADMAP D2, S6), which also owe the on-chip
byte-identity check. Correctness is pinned against
device_sort.merge_two_sorted by tests/test_pallas_merge.py (interpret
mode).

Reference seam: the comparator loop inside RocksDB CompactRange
(reference src/server/pegasus_server_impl.cpp:2814-2891).
"""

import functools
import os

import numpy as np

from .device_sort import _partner_concat, lex_cmp


def pallas_enabled() -> bool:
    """Default OFF (see module docstring: it compiles and matches on the
    chip; turning it on is a measured perf change). PEGASUS_PALLAS=1
    enables."""
    return os.environ.get("PEGASUS_PALLAS") == "1"


CHUNK = 2048   # output rows per program
LANES = 128
TILE = 1024    # Mosaic 1D i32 VMEM tiling: DMA offsets must be multiples
WINDOW = CHUNK + TILE          # elements DMA'd per side per program
MERGE_ROWS = (4 * CHUNK) // LANES  # 2*WINDOW padded up to pow2, in rows
HALF_ROWS = CHUNK // LANES     # rows in one output chunk
WIN_ROWS = WINDOW // LANES


def _lex_less_at(cols_a, ia, cols_b, ib):
    """Strict a[ia] < b[ib], vectorized over index arrays (jnp)."""
    import jax.numpy as jnp

    less = jnp.zeros(ia.shape, dtype=bool)
    eq = jnp.ones(ia.shape, dtype=bool)
    for ca, cb in zip(cols_a, cols_b):
        va = jnp.take(ca, ia, mode="clip")
        vb = jnp.take(cb, ib, mode="clip")
        less = less | (eq & (va < vb))
        eq = eq & (va == vb)
    return less


def _diagonal_splits(a_cols, b_cols, nk, n_chunks):
    """ai[p] for output diagonals d = p*CHUNK (bi = d - ai). Standard
    merge-path binary search on the cross-diagonal predicate."""
    import jax.numpy as jnp

    la = a_cols[0].shape[0]
    lb = b_cols[0].shape[0]
    d = jnp.arange(n_chunks, dtype=jnp.int32) * CHUNK
    lo = jnp.maximum(0, d - lb)
    hi = jnp.minimum(d, la)
    # invariant: the split ai is the count of A-elements among the first d
    # of the merged order = |{i : A[i] < B[d-1-i]}| along the diagonal;
    # binary search the monotone predicate A[mid] < B[d-1-mid]
    steps = max(1, int(np.ceil(np.log2(max(2, min(la, lb) + 1)))) + 1)
    for _ in range(steps):
        active = lo < hi
        mid = (lo + hi) // 2
        take_a = _lex_less_at(a_cols[:nk], mid, b_cols[:nk], d - 1 - mid)
        lo = jnp.where(active & take_a, mid + 1, lo)
        hi = jnp.where(active & ~take_a, mid, hi)
    return lo  # == hi


def _lane_permute(c, perm_of_lane):
    """Apply out[.., l] = c[.., p] where perm_of_lane(p) == l, via the
    MXU: multiply by the 128x128 one-hot permutation built in-kernel from
    iotas, u32 split into u8 quarters so bf16 accumulation is exact.
    Mosaic has no uint32<->bfloat16 casts: quarters route through int32
    (bitcast; values 0..255) -> f32 -> bf16, and the f32 matmul result
    back through int32."""
    import jax.numpy as jnp
    from jax import lax

    pr = lax.broadcasted_iota(jnp.uint32, (LANES, LANES), 0)
    pc = lax.broadcasted_iota(jnp.uint32, (LANES, LANES), 1)
    one = jnp.ones((LANES, LANES), jnp.float32)
    p = jnp.where(pr == perm_of_lane(pc), one, 0.0).astype(jnp.bfloat16)
    bits = lax.bitcast_convert_type(c, jnp.uint32)
    out = None
    for s in (0, 8, 16, 24):
        q = (bits >> s) & jnp.uint32(0xFF)
        qf = lax.bitcast_convert_type(q, jnp.int32).astype(
            jnp.float32).astype(jnp.bfloat16)
        sq = lax.dot(qf, p, preferred_element_type=jnp.float32)
        sq = lax.bitcast_convert_type(sq.astype(jnp.int32), jnp.uint32) << s
        out = sq if out is None else out | sq
    return lax.bitcast_convert_type(out, c.dtype)


def _lane_partner(c, j):
    """Partner copy at lane distance j (< 128): XOR-j lane permutation."""
    import jax.numpy as jnp

    return _lane_permute(c, lambda l: l ^ jnp.uint32(j))


def _flat_reverse(c, rows):
    """Reverse a (rows, LANES) buffer in FLAT element order (k -> L-1-k):
    reverse the row order (concat of row slices — Mosaic has no rev
    primitive) then reverse within lanes (one-hot permutation matmul)."""
    import jax.numpy as jnp

    if rows > 1:
        c = jnp.concatenate([c[r : r + 1] for r in range(rows - 1, -1, -1)],
                            axis=0)
    return _lane_permute(c, lambda l: jnp.uint32(LANES - 1) - l)


def _merge_2d(cols, nk, rows):
    """Bitonic merge of a (rows, LANES) bitonic buffer, flat order
    k = row*LANES + lane, ascending in the first nk columns."""
    import jax.numpy as jnp
    from jax import lax

    rows_iota = lax.broadcasted_iota(jnp.uint32, (rows, LANES), 0)
    lanes_iota = lax.broadcasted_iota(jnp.uint32, (rows, LANES), 1)
    j = (rows * LANES) // 2
    while j >= 1:
        if j >= LANES:
            # row-block swap at distance jr: _partner_concat slices the
            # leading axis, so it works unchanged on the (rows, LANES)
            # layout (and avoids tiny-dim reshapes Mosaic lowers poorly)
            jr = j // LANES
            is_high = (rows_iota & jnp.uint32(jr)) != 0
            px = [_partner_concat(c, jr) for c in cols]
        else:
            is_high = (lanes_iota & jnp.uint32(j)) != 0
            px = [_lane_partner(c, j) for c in cols]
        p_lt, p_eq = lex_cmp(px[:nk], cols[:nk])
        p_gt = ~p_lt & ~p_eq
        # boolean algebra, not where(): Mosaic cannot select between i1
        # vectors (i8->i1 trunci is unsupported)
        take_p = (is_high & p_gt) | (~is_high & p_lt)
        cols = [jnp.where(take_p, pc, c) for c, pc in zip(cols, px)]
        j //= 2
    return cols


@functools.lru_cache(maxsize=64)
def _compiled_merge(la, lb, n_ops, nk, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    L_out = la + lb
    n_chunks = -(-L_out // CHUNK)

    def kernel(al_ref, bl_ref, fill_ref, *refs):
        p = pl.program_id(0)
        a_refs = refs[:n_ops]
        b_refs = refs[n_ops : 2 * n_ops]
        out_refs = refs[2 * n_ops : 3 * n_ops]
        # al/bl hold ROW offsets (elements // LANES), multiples of
        # TILE // LANES = 8 — exactly the (8, 128) VMEM tile row count
        ar0 = pl.multiple_of(al_ref[p], TILE // LANES)
        br0 = pl.multiple_of(bl_ref[p], TILE // LANES)
        if interpret:
            a_cols = [ar[pl.ds(ar0, WIN_ROWS)] for ar in a_refs]
            b_cols = [br[pl.ds(br0, WIN_ROWS)] for br in b_refs]
        else:
            from jax.experimental.pallas import tpu as pltpu

            scratch = refs[3 * n_ops : 5 * n_ops]
            sem = refs[5 * n_ops]
            copies = []
            for i in range(n_ops):
                copies.append(pltpu.make_async_copy(
                    a_refs[i].at[pl.ds(ar0, WIN_ROWS)], scratch[i],
                    sem.at[2 * i]))
                copies.append(pltpu.make_async_copy(
                    b_refs[i].at[pl.ds(br0, WIN_ROWS)],
                    scratch[n_ops + i], sem.at[2 * i + 1]))
            for c in copies:
                c.start()
            for c in copies:
                c.wait()
            a_cols = [s[...] for s in scratch[:n_ops]]
            b_cols = [s[...] for s in scratch[n_ops : 2 * n_ops]]
        # bitonic input: A window ascending, pad fill (sorts last), B
        # window reversed in flat order — pow2 total of MERGE_ROWS rows
        pad_rows = MERGE_ROWS - 2 * WIN_ROWS
        cols = []
        for i, (a, b) in enumerate(zip(a_cols, b_cols)):
            fill = jnp.full((pad_rows, LANES), fill_ref[i], a.dtype)
            cols.append(jnp.concatenate(
                [a, fill, _flat_reverse(b, WIN_ROWS)], axis=0))
        cols = _merge_2d(cols, nk, MERGE_ROWS)
        # delta = d - al - bl is 0 or TILE (see module docstring): the
        # output chunk is one of two static row windows
        delta_rows = jnp.int32(p) * HALF_ROWS - ar0 - br0
        hi = delta_rows > 0
        for out_ref, c in zip(out_refs, cols):
            lo_w = c[:HALF_ROWS]
            hi_w = c[TILE // LANES : TILE // LANES + HALF_ROWS]
            out_ref[...] = jnp.where(hi, hi_w, lo_w)

    def row_pad(c, f):
        """Pad so every aligned WINDOW row-range is in bounds, rounded up
        to whole LANES rows, and reshape to (rows, LANES)."""
        import jax.numpy as jnp

        n = c.shape[0]
        total = -(-(n + WINDOW) // LANES) * LANES
        return jnp.concatenate(
            [c, jnp.full((total - n,), f, c.dtype)]).reshape(-1, LANES)

    def fn(a_ops, b_ops, pad_fill):
        # pads sort last and merge-path never assigns them a real chunk
        a_pad = [row_pad(c, f) for c, f in zip(a_ops, pad_fill)]
        b_pad = [row_pad(c, f) for c, f in zip(b_ops, pad_fill)]
        ai = _diagonal_splits(a_ops, b_ops, nk, n_chunks)
        bi = jnp.arange(n_chunks, dtype=jnp.int32) * CHUNK - ai
        # row offsets of the tile-aligned windows
        al = ((ai // TILE) * TILE) // LANES
        bl = ((bi // TILE) * TILE) // LANES
        # per-column pad fill as an SMEM input; i32 bit patterns (the
        # kernel's jnp.full converts back to each column dtype, wrapping)
        fills = jnp.stack(
            [jnp.asarray(f).astype(jnp.int32) for f in pad_fill])

        out_shapes = [
            jax.ShapeDtypeStruct((n_chunks * HALF_ROWS, LANES), c.dtype)
            for c in a_ops
        ]
        out_specs = [
            pl.BlockSpec((HALF_ROWS, LANES), lambda p: (p, 0))
            for _ in a_ops
        ]
        if interpret:
            in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (3 + 2 * n_ops)
            scratch_shapes = []
        else:
            from jax.experimental.pallas import tpu as pltpu

            in_specs = (
                [pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
                + [pl.BlockSpec(memory_space=pl.ANY)] * (2 * n_ops)
            )
            scratch_shapes = (
                [pltpu.VMEM((WIN_ROWS, LANES), c.dtype) for c in a_ops] * 2
                + [pltpu.SemaphoreType.DMA((2 * n_ops,))]
            )
        merged = pl.pallas_call(
            kernel,
            grid=(n_chunks,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            scratch_shapes=scratch_shapes,
            interpret=interpret,
        )(al, bl, fills, *a_pad, *b_pad)
        return [m.reshape(-1)[:L_out] for m in merged]

    # plain jit on purpose: the pipeline calls this inside its own trace,
    # so it is inlined into that DeviceKernel's program and never compiles
    # on its own under a lane guard (standalone calls are tests only)
    return jax.jit(fn)


def merge_two_sorted_pallas(a_ops, b_ops, nk, pad_fill):
    """Drop-in for device_sort.merge_two_sorted (returns exactly la+lb rows,
    ascending; same strict-total-order requirement on the key columns)."""
    import jax

    la = int(a_ops[0].shape[0])
    lb = int(b_ops[0].shape[0])
    interpret = jax.default_backend() != "tpu"
    fn = _compiled_merge(la, lb, len(a_ops), nk, interpret)
    return fn(tuple(a_ops), tuple(b_ops), tuple(pad_fill))
