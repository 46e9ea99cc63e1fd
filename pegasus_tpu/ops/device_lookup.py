"""Batched point lookups over HBM-resident SST key columns.

The compaction side of the LSM already lives on the device: flush and
compaction prime each run's packed key columns into HBM (`DeviceRun`,
ops/compact.py) and merge them there. This module serves the OTHER half
of the LSM from the same resident data (CompassDB's argument, PAPERS.md:
build the read index as a byproduct of compaction, exactly when the
sorted key column is already on the chip): `get`/`multi_get`/`batch_get`
point reads become one vmapped probe kernel per SST instead of a Python
binary search per key.

Two pieces:

  1. A per-SST FENCE-POINTER index (`build_fence_index`), computed on
     device from the already-resident sorted first key lane as a
     byproduct of the flush/compaction prime (pack_run_device): every
     `step`-th first-lane value is sampled into a small fence array.
     A query's two searchsorted probes against the fence bound its
     position to one `step`-sized block of the run — the CompassDB
     perfect-hash role, filled by the structure we get for free from
     sortedness. (A true minimal perfect hash over full keys needs a
     host pass over the key bytes; the fence needs nothing the chip
     does not already hold.)
  2. A batched lookup kernel (`lookup_batch`): queries are packed into
     the run's uint32 prefix lanes (the same packing the merge sort
     keys use — DeviceRun runs hold the FULL key in their lanes, up to
     the window's 64-byte cap, so lane+klen equality IS full-key
     equality), fenced, then resolved with a fixed-depth vectorized
     binary search. Returns each query's row index in the run, or -1.
  3. A batched range kernel (`range_batch`): the same fence-bounded
     lower_bound run over a batch of (start, stop) bounds, resolving
     each range query to the run's contiguous row interval [lo, hi) in
     one dispatch — the device half of engine scan_range_batch
     (multi_get hash ranges, sortkey_count, scanner batches).

ONE UPLOAD A CALL (`_device_call`): a read call hands the chip one host
array, the packed query image `uint32[(w + 1), qpad]` (rows 0..w-1 the
lanes, row w the lengths; a range call stacks its two bounds), and
launches one program. The run's two scalars (`n`, the fence step) were
made on the device with the fence and stay on the `DeviceRun`. A round
through the runtime costs a handler thread the GIL, which sixteen others
want back (PERF.md section 5), so what a call costs is how many rounds
it makes, not the bytes that cross.

The kernel returns INDICES only; the host materializes values from the
SST's cached block exactly like the host binary search does, so the
device path is byte-identical to `SSTable.find` by construction. Every
batched probe runs under the read lane guard (runtime/lane_guard.py
READ_LANE_GUARD) from engine/db.py — deadline, retry, breaker, host
fallback — and fires the `read.device` fail point for chaos tests.
"""

import functools

import numpy as np

from ..runtime.fail_points import inject as _inject
from ..runtime.perf_counters import counters
from ..runtime.tracing import COMPACT_TRACER as _TRACE
from .compact import _pow2ceil
from .kernel import DeviceKernel
from .packing import pack_key_prefixes

_FENCE_MAX = 4096     # fence entries per run (16 KiB of HBM at the cap)
_QUERY_MIN_BUCKET = 8  # pad query batches to pow2 buckets >= this

# probe totals resolved once — this path fires per coalesced batch
_C_LOOKUPS = counters.number("read.device.lookup_count")
_C_KEYS = counters.number("read.device.keys")
_C_HITS = counters.number("read.device.hits")
# range_batch kernel dispatches: the range twin of lookup_count. A range
# query counts in read.range.device_count as soon as the device path was
# ELIGIBLE; only this says the interval-resolve kernel actually ran (an
# SST whose candidate set is under the min-batch floor resolves on the
# host inside a "device" query)
_C_RANGE_DISPATCH = counters.number("read.range.dispatch_count")
# (range, SST) bounds the range kernel really resolved: what a dispatch
# carried. Its host twin, read.range.host_ranges (engine/db.py), counts
# the bounds SSTable.lower_bound walked, inside a "device" batch or not
_C_RANGE_DEVICE_RANGES = counters.number("read.range.device_ranges")
# monotonic total of runs left host-served by a failed fence build
_C_FENCE_FAIL = counters.number("read.device.fence_fail_count")


def _fence_lower_bound(jnp, lex_less, padded_len, w, fence_len, steps,
                       cols, klen, fence, n, step, qcols, qklen):
    """Trace-time shared core of the point and range kernels: fence probe
    -> fixed-depth vectorized lower_bound over the full (prefix lanes,
    klen) sort key. Returns each query's lower_bound row index in [0, n]
    (n = every row < query). Runs hold the FULL key in their lanes
    (pack_run_device refuses otherwise), so lane/klen lex order IS byte
    order and the result matches SSTable.lower_bound exactly — including
    for queries LONGER than the 4*w-byte window: such a query's lane
    image ties only with rows that are proper byte prefixes of it, and
    the klen tiebreak orders those below the query, same as bytes."""
    import jax

    with jax.named_scope("pegasus_fence_lower_bound"):
        return _fence_lower_bound_body(jnp, lex_less, padded_len, w,
                                       fence_len, steps, cols, klen, fence,
                                       n, step, qcols, qklen)


def _fence_lower_bound_body(jnp, lex_less, padded_len, w, fence_len, steps,
                            cols, klen, fence, n, step, qcols, qklen):
    q0 = qcols[0]
    # fence window: rows before sample a-1 are < q0, rows from sample
    # b on are > q0, so the full-key lower_bound lies in [lo, hi)
    a = jnp.searchsorted(fence, q0, side="left").astype(jnp.int32)
    b = jnp.searchsorted(fence, q0, side="right").astype(jnp.int32)
    n1 = n - 1
    lo = jnp.where(a > 0, jnp.minimum((a - 1) * step, n1), 0)
    hi = jnp.where(b < fence_len, jnp.minimum(b * step, n1), n)
    length = jnp.maximum(hi - lo, 0)
    qkey = list(qcols) + [qklen]
    for _ in range(steps):
        half = length >> 1
        mid = lo + half
        midc = jnp.minimum(mid, padded_len - 1)
        row = [jnp.take(cols[j], midc) for j in range(w)] \
            + [jnp.take(klen, midc)]
        less = lex_less(row, qkey)
        active = length > 0
        lo = jnp.where(active & less, mid + 1, lo)
        length = jnp.where(active,
                           jnp.where(less, length - half - 1, half),
                           0)
    return lo


@functools.lru_cache(maxsize=64)
def _compiled_fence_build(padded_len: int, fence_len: int):
    import jax.numpy as jnp
    from jax import lax

    def fn(col0, n, step):
        pos = lax.iota(jnp.int32, fence_len) * step
        return jnp.take(col0, jnp.minimum(pos, n - 1))

    return DeviceKernel(fn, "fence_build")


def build_fence_index(dr) -> bool:
    """Attach the fence-pointer index to a DeviceRun in place (fields
    `fence`, `fence_step`, `fence_len`, and the device scalars `n_dev`,
    `step_dev` the build made of `n` and the step: every read call of
    the run's life passes those two, so none makes its own). Computed on
    device from the resident first key lane — the compaction/flush pass
    calls this right after the upload, so the index is a byproduct of
    work already done. Returns False (and leaves the run index-less,
    i.e. host-served) on any backend failure."""
    import jax.numpy as jnp

    if dr is None or dr.n == 0:
        return False
    fence_len = min(_FENCE_MAX, _pow2ceil(max(1, dr.n // 8), 16))
    step = -(-dr.n // fence_len)  # ceil: fence_len * step >= n
    try:
        fn = _compiled_fence_build(dr.padded_len, fence_len)
        dr.n_dev, dr.step_dev = jnp.int32(dr.n), jnp.int32(step)
        dr.fence = fn(dr.cols[0], dr.n_dev, dr.step_dev)
        dr.fence_step = step
        dr.fence_len = fence_len
        return True
    except Exception as e:  # noqa: BLE001 - an index-less run is just host-served
        _C_FENCE_FAIL.increment()
        print(f"[device-lookup] fence build failed: {e!r}", flush=True)
        dr.fence = dr.n_dev = dr.step_dev = None
        return False


def _image_rows(image, w: int):
    """Trace-time: one packed query image `uint32[(w + 1), qpad]` -> the
    (qcols, qklen) that _fence_lower_bound takes."""
    return [image[j] for j in range(w)], image[w]


@functools.lru_cache(maxsize=256)
def _compiled_lookup(padded_len: int, w: int, fence_len: int, qpad: int):
    """Jitted batched point lookup for one (run shape, query bucket):
    fence probe -> fixed-depth vectorized lower_bound over the full
    (prefix lanes, klen) sort key -> exact-equality check. Keyed on the
    padded bucket lengths only, so a live engine's varying run/batch
    sizes share programs (the compaction pipeline's recompile rule)."""
    import jax.numpy as jnp

    from .device_sort import lex_less

    steps = max(1, padded_len.bit_length())

    def fn(cols, klen, fence, n, step, image):
        import jax

        qcols, qklen = _image_rows(image, w)
        lo = _fence_lower_bound(jnp, lex_less, padded_len, w, fence_len,
                                steps, cols, klen, fence, n, step,
                                qcols, qklen)
        with jax.named_scope("pegasus_lookup_match"):
            safe = jnp.minimum(lo, padded_len - 1)
            eq = lo < n
            for j in range(w):
                eq &= jnp.take(cols[j], safe) == qcols[j]
            eq &= jnp.take(klen, safe) == qklen
            return jnp.where(eq, lo, jnp.int32(-1))

    return DeviceKernel(fn, "lookup")


def pack_queries(keys, w: int) -> np.ndarray:
    """Host-side packing of query keys into a run's lane layout: -> ONE
    uint32[(w + 1), qpad] image, rows 0..w-1 the lanes and row w the key
    lengths, zero-padded to the pow2 query bucket. A query longer than
    the run's 4*w-byte window truncates in the lanes but keeps its true
    klen — it can never equal a resident key (all <= 4*w bytes), so the
    equality check still returns -1 for it, which is the correct answer."""
    n = len(keys)
    arena = np.frombuffer(b"".join(keys), dtype=np.uint8).copy() \
        if n else np.zeros(0, np.uint8)
    lens = np.fromiter((len(k) for k in keys), dtype=np.int32, count=n)
    offs = np.zeros(n, dtype=np.int64)
    if n:
        np.cumsum(lens[:-1], out=offs[1:])
    image = np.zeros((w + 1, _pow2ceil(max(1, n), _QUERY_MIN_BUCKET)),
                     np.uint32)
    image[:w, :n] = pack_key_prefixes(arena, offs, lens, w).T
    image[w, :n] = lens
    return image


def _upload(image: np.ndarray):
    """The one host->device transfer of a read call."""
    import jax

    return jax.device_put(image)


def _device_call(stage: str, dr, compiled, nq: int, pack) -> np.ndarray:
    """The three parts of one device read call, each a span of its own
    under the caller's `stage` span: the host packs the queries into one
    image (`pack()`), uploads it and launches the program (asynchronous),
    then waits for the few bytes a query comes to. Nothing else crosses:
    the run's columns, fence and scalars are resident."""
    with _TRACE.span(stage + ".pack", records=nq):
        image = pack()
    with _TRACE.span(stage + ".dispatch", records=nq):
        fn = compiled(dr.padded_len, dr.w, dr.fence_len, image.shape[-1])
        out = fn(tuple(dr.cols), dr.klen, dr.fence, dr.n_dev, dr.step_dev,
                 _upload(image))
    with _TRACE.span(stage + ".download", records=nq):
        return np.asarray(out)


def lookup_batch(dr, keys) -> np.ndarray:
    """Probe `keys` (list of full stored keys, any order) against one
    HBM-resident run. -> np.int32[len(keys)]: the run row index of each
    exact match, -1 for absent keys. Raises on device failure — the
    caller (engine/db.py get_batch) runs this under READ_LANE_GUARD with
    the host binary-search walk as the byte-identical fallback."""
    if not keys or dr is None or dr.fence is None:
        return np.full(len(keys), -1, np.int32)
    with _TRACE.span("read.device", records=len(keys)):
        _inject("read.device")
        rows = _device_call("read.device", dr, _compiled_lookup, len(keys),
                            lambda: pack_queries(keys, dr.w))[: len(keys)]
    _C_LOOKUPS.increment()
    _C_KEYS.increment(len(keys))
    _C_HITS.increment(int((rows >= 0).sum()))
    return rows


@functools.lru_cache(maxsize=256)
def _compiled_range(padded_len: int, w: int, fence_len: int, qpad: int):
    """Jitted batched range resolve for one (run shape, query bucket):
    the point kernel's fence-bounded lower_bound run TWICE — once over
    the start keys, once over the stop keys, the two images of one
    uint32[2, w + 1, qpad] — in one program, yielding each query's
    contiguous row interval [lo, hi). Keyed on the padded bucket lengths
    like _compiled_lookup so live sizes share programs."""
    import jax.numpy as jnp

    from .device_sort import lex_less

    steps = max(1, padded_len.bit_length())

    def fn(cols, klen, fence, n, step, images):
        def bound(image):
            return _fence_lower_bound(jnp, lex_less, padded_len, w,
                                      fence_len, steps, cols, klen, fence,
                                      n, step, *_image_rows(image, w))

        lo, hi = bound(images[0]), bound(images[1])
        # a stop below the start (empty/inverted range) clamps to empty
        return jnp.stack([lo, jnp.maximum(hi, lo)])

    return DeviceKernel(fn, "range")


def range_batch(dr, ranges) -> np.ndarray:
    """Resolve each (start_key, stop_key) query against one HBM-resident
    run: -> np.int32[(len(ranges), 2)], each row the run's contiguous
    row interval [lo, hi) holding exactly the keys in [start, stop).
    stop_key None means "to the end of the run". Both bounds cross in
    ONE upload, resolve in ONE kernel dispatch and come back in ONE
    download per run per batch. Raises on device failure — the caller
    (engine/db.py scan_range_batch) runs this under READ_LANE_GUARD with
    the host SSTable.lower_bound walk as the byte-identical fallback."""
    nq = len(ranges)
    if not nq or dr is None or dr.fence is None:
        return np.zeros((nq, 2), np.int32)
    starts = [s for s, _ in ranges]
    stops = [(t if t is not None else b"") for _, t in ranges]
    open_stop = np.fromiter((t is None for _, t in ranges),
                            dtype=bool, count=nq)
    with _TRACE.span("read.range", records=nq):
        _inject("read.range")
        iv = _device_call(
            "read.range", dr, _compiled_range, nq,
            lambda: np.stack([pack_queries(starts, dr.w),
                              pack_queries(stops, dr.w)]))[:, :nq].T.copy()
    _C_RANGE_DISPATCH.increment()
    _C_RANGE_DEVICE_RANGES.increment(nq)
    # a None stop packed as b"" would lower_bound to 0; patch to run end
    iv[open_stop, 1] = dr.n
    return iv
