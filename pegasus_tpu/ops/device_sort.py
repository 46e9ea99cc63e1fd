"""Bitonic merge/sort networks shaped for the TPU memory system.

XLA's native `lax.sort` is unusable here: its TPU lowering unrolls per
element and did not finish compiling a [64, 16384] sort in minutes on v5e.
So the engine emits its own compare-exchange networks as O(log n) /
O(log^2 n) vectorized stages. What makes this file different from a
textbook bitonic sort is that every stage is chosen for how it maps onto
the TPU's (8, 128) tiled memory and compute units, measured on chip:

- Every materialized buffer is FLAT [L]. Round 1 reshaped stages to
  [blocks, 2, j], whose tiny minor dims tile-pad up to 64x and OOM'd HBM
  at 2M records (BENCH_r01). Here the partner operand is produced as a
  flat permuted copy and the compare/select runs full-length elementwise,
  so nothing padded is ever materialized.
- Exchange distance j < 128 (intra-lane) is done on the MXU: a 128x128
  XOR-permutation matrix applied by matmul, with u32 values split into
  u8 quarters so bf16 accumulation is exact. Measured 1.9 ms/stage at
  8M rows x 9 columns (318 GB/s) vs 174 ms for the strided-reshape form.
- Mid-range j uses the strided-reshape partner copy (130-195 GB/s).
- Huge j (fewer than 8 blocks) uses explicit flat slice+concat, which
  lowers to large contiguous copies instead of sublane-padded reshapes.

The networks sort lexicographically by the first `nk` columns (uint32,
most significant first) and carry the remaining columns as payload.
Compaction inputs are already-sorted runs, so the hot path is
`merge_network` — log2(L) stages — not the full log^2 sort; the full
`sort_network` exists for unsorted single runs (memtable flush).

Reference seam: this replaces the comparator loop inside RocksDB
compaction/flush (reference src/server/pegasus_server_impl.cpp:2814
CompactRange; rocksdb memtable sort) with batched device passes.
"""

import functools

import numpy as np

_MXU_MIN_L = 1024  # below this, strided reshapes are cheap enough


def lex_less(a_cols, b_cols):
    """Strict lexicographic a < b over uint32 column lists, vectorized."""
    return lex_cmp(a_cols, b_cols)[0]


def lex_cmp(a_cols, b_cols):
    """(a < b, a == b) lexicographic over uint32 column lists, vectorized.

    Seeded from the first column's comparison rather than boolean constant
    arrays: Mosaic (pallas TPU) cannot materialize i1 vector constants
    (i8->i1 trunci is unsupported), and this form is equivalent."""
    less = a_cols[0] < b_cols[0]
    eq = a_cols[0] == b_cols[0]
    for a, b in zip(a_cols[1:], b_cols[1:]):
        less = less | (eq & (a < b))
        eq = eq & (a == b)
    return less, eq


@functools.lru_cache(maxsize=16)
def _perm_matrix(j: int):
    """128x128 one-hot XOR-j permutation, exact in bf16."""
    p = np.zeros((128, 128), np.float32)
    for k in range(128):
        p[k, k ^ j] = 1.0
    return p


def _partner_mxu(c, j):
    """Partner copy for j < 128 via MXU matmul. u32 split into u8 quarters:
    one-hot rows make each output a single u8 term, exact in bf16."""
    import jax.numpy as jnp
    from jax import lax

    p = jnp.asarray(_perm_matrix(j), dtype=jnp.bfloat16)
    bits = lax.bitcast_convert_type(c, jnp.uint32)
    x = bits.reshape(-1, 128)
    out = None
    for s in (0, 8, 16, 24):
        q = ((x >> s) & jnp.uint32(0xFF)).astype(jnp.bfloat16)
        sq = lax.dot(q, p).astype(jnp.uint32) << s
        out = sq if out is None else out | sq
    return lax.bitcast_convert_type(out.reshape(c.shape), c.dtype)


def _partner_reshape(c, j):
    """Partner copy via [blocks, 2, j] axis flip; flat in/out buffers."""
    L = c.shape[0]
    return c.reshape(L // (2 * j), 2, j)[:, ::-1, :].reshape(L)


def _partner_concat(c, j):
    """Partner copy via explicit flat slice swaps (for <8 blocks: the
    reshape form would sublane-pad; contiguous copies don't)."""
    import jax.numpy as jnp

    L = c.shape[0]
    parts = []
    for b in range(L // (2 * j)):
        lo, hi = 2 * b * j, (2 * b + 1) * j
        parts.append(c[hi : hi + j])
        parts.append(c[lo:hi])
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _partner(c, j, mxu=True):
    L = c.shape[0]
    if mxu and j < 128 and L >= _MXU_MIN_L and _on_tpu():
        # intra-lane exchange: only worth the matmul machinery where lane
        # padding exists; on CPU the strided reshape is cheap and compiles
        # far faster
        return _partner_mxu(c, j)
    if L // (2 * j) < 8:
        return _partner_concat(c, j)
    return _partner_reshape(c, j)


def _exchange(cols, nk, j, flip, mxu=True):
    """One compare-exchange stage at distance j. flip = is_high ^ is_desc.
    Comparisons are strict both ways so equal pairs stay put (a non-strict
    form would copy one element over both slots, corrupting payloads).
    mxu=False forces the reshape/concat partner forms (used inside Pallas
    kernels, where data is already VMEM-resident)."""
    import jax.numpy as jnp

    px = [_partner(c, j, mxu=mxu) for c in cols]
    p_lt, p_eq = lex_cmp(px[:nk], cols[:nk])
    p_gt = ~p_lt & ~p_eq
    take_p = jnp.where(flip, p_gt, p_lt)
    return [jnp.where(take_p, pc, c) for c, pc in zip(cols, px)]


def merge_network(cols, nk):
    """Sort a BITONIC input (ascending run then descending run) ascending.

    log2(L) stages. This is the compaction hot path: two sorted runs
    become bitonic via concat(A, reverse(B)) (pad in the middle stays
    bitonic). L must be a power of two."""
    import jax
    from jax import lax

    L = cols[0].shape[0]
    if L & (L - 1):
        raise ValueError(f"merge_network needs power-of-two length, got {L}")
    with jax.named_scope("pegasus_merge_network"):
        iota = lax.iota(np.uint32, L)
        j = L // 2
        while j >= 1:
            is_high = (iota & np.uint32(j)) != 0
            cols = _exchange(cols, nk, j, is_high)
            j //= 2
    return cols


def sort_network(cols, nk):
    """Full bitonic sort, ascending. log2(L)*(log2(L)+1)/2 stages; used for
    unsorted single runs (flush). L must be a power of two."""
    import jax
    from jax import lax

    L = cols[0].shape[0]
    if L & (L - 1):
        raise ValueError(f"sort_network needs power-of-two length, got {L}")
    if L == 1:
        return list(cols)
    with jax.named_scope("pegasus_sort_network"):
        iota = lax.iota(np.uint32, L)
        k = 2
        while k <= L:
            is_desc = (iota & np.uint32(k)) != 0 if k < L else None
            j = k // 2
            while j >= 1:
                is_high = (iota & np.uint32(j)) != 0
                flip = is_high if is_desc is None else is_high ^ is_desc
                cols = _exchange(cols, nk, j, flip)
                j //= 2
            k *= 2
    return cols


def merge_two_sorted(a_cols, b_cols, nk, pad_fill):
    """Merge two ascending-sorted column sets into one ascending set of
    power-of-two length >= la + lb. Padding (pad_fill per column, which must
    sort after all real rows) is inserted between the ascending and the
    reversed descending half, which preserves bitonicity; pads sort to the
    tail. Returns padded merged columns (caller trims to la + lb)."""
    import jax
    import jax.numpy as jnp

    la, lb = a_cols[0].shape[0], b_cols[0].shape[0]
    L = 1
    while L < la + lb:
        L <<= 1
    npad = L - la - lb
    merged = []
    with jax.named_scope("pegasus_bitonic_concat"):
        for a, b, fill in zip(a_cols, b_cols, pad_fill):
            mid = jnp.full((npad,), fill, dtype=a.dtype)
            merged.append(jnp.concatenate([a, mid, b[::-1]]))
    return merge_network(merged, nk)
