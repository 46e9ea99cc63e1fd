"""Pallas merge-path kernel: interpret-mode equivalence with the XLA merge.

Runs on the CPU mesh in pallas interpret mode (the correctness pin that
needs no chip); the Mosaic-lowered TPU body's byte comparison on the chip
belongs to ROADMAP S6's pairs. PEGASUS_PALLAS=1 turns the
kernel on (default off; =1 means interpret mode on CPU).
"""

import numpy as np
import pytest

from pegasus_tpu.ops import pallas_merge
from pegasus_tpu.ops.device_sort import merge_two_sorted

NCOLS = 4


def make_sorted(rng, n, lo=0, hi=1 << 20):
    prim = np.sort(rng.integers(lo, hi, size=n, dtype=np.uint32))
    rest = [rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
            for _ in range(NCOLS - 1)]
    order = np.lexsort(tuple(reversed([prim] + rest)))
    return [c[order] for c in [prim] + rest]


@pytest.mark.parametrize("la,lb,seed", [
    (1000, 1000, 0),
    (1, 5000, 1),
    (5000, 1, 2),
    (3000, 7001, 3),
    (2048, 2048, 4),          # exact chunk multiples
    (pallas_merge.CHUNK * 2 + 17, pallas_merge.CHUNK - 3, 5),
])
def test_pallas_merge_matches_xla_merge(la, lb, seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    A, B = make_sorted(rng, la), make_sorted(rng, lb)
    pad_fill = tuple([np.uint32(0xFFFFFFFF)] * NCOLS + [np.int32(-1)])
    a_ops = [jnp.asarray(c) for c in A] + [jnp.arange(la, dtype=jnp.int32)]
    b_ops = [jnp.asarray(c) for c in B] + [
        jnp.arange(la, la + lb, dtype=jnp.int32)]
    got = pallas_merge.merge_two_sorted_pallas(a_ops, b_ops, NCOLS, pad_fill)
    want = merge_two_sorted(a_ops, b_ops, NCOLS, pad_fill)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g)[: la + lb],
                                      np.asarray(w)[: la + lb])


def test_pallas_merge_skewed_distributions():
    """Disjoint ranges + heavy overlap: diagonal search edge cases."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    pad_fill = tuple([np.uint32(0xFFFFFFFF)] * NCOLS + [np.int32(-1)])
    for A, B in [
        (make_sorted(rng, 4000, 0, 1000), make_sorted(rng, 4000, 10_000, 11_000)),
        (make_sorted(rng, 4000, 10_000, 11_000), make_sorted(rng, 4000, 0, 1000)),
        (make_sorted(rng, 4096, 5, 6), make_sorted(rng, 4096, 5, 6)),
    ]:
        la, lb = len(A[0]), len(B[0])
        a_ops = [jnp.asarray(c) for c in A] + [jnp.arange(la, dtype=jnp.int32)]
        b_ops = [jnp.asarray(c) for c in B] + [
            jnp.arange(la, la + lb, dtype=jnp.int32)]
        got = pallas_merge.merge_two_sorted_pallas(a_ops, b_ops, NCOLS, pad_fill)
        want = merge_two_sorted(a_ops, b_ops, NCOLS, pad_fill)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g)[: la + lb],
                                          np.asarray(w)[: la + lb])
