"""Bounded double-buffered compaction pipeline executor.

Every compaction path used to pay ``sum(pack + h2d + device + gather +
sst_write)`` per range/level even though the stages run on disjoint
resources (host CPU, PCIe, device, host memcpy, disk). LUDA
(arXiv 2004.03054) shows device-offloaded LSM compaction only wins when
the CPU-side stages are pipelined against device work; RESYSTANCE
(arXiv 2603.05162) shows serialized compaction stages leave large
fractions of the hardware idle. This module is the one executor all
three serial loops thread through:

  - ``ops/compact.py::_compact_blockwise`` — while range *i* runs its
    device merge, range *i+1* packs/uploads on a host worker and range
    *i−1* gathers/post-filters on another;
  - ``engine/db.py`` — the SST write + manifest install of level output
    *k* overlaps the merge of *k+1* (deferred installs), and the
    flush-time device-residency prime rides the pool instead of the
    write path;
  - ``ops/batched_compact.py`` — the next partition batch's host
    stacking prefetches under the current batch's device dispatch.

Shape: ``map(items, prefetch, dispatch, finish)`` runs ``prefetch`` on a
shared host worker pool (``runtime/tasking.ThreadPool``), ``dispatch``
in the CALLING thread (device work — so a lane-guard wrapper around the
whole map keeps its deadline/abandon/fallback semantics, and a single
abandoned thread abandons the whole pipeline), and ``finish`` on a host
worker again. Depth is bounded (``PEGASUS_COMPACT_PIPELINE_DEPTH``,
default 2 = one in-flight prefetch) so HBM headroom per
``max_device_records`` is preserved: at most ``depth`` ranges are
resident at once. Depth 1 degenerates to the serial loop.

Failure contract: any stage error drains the pipeline (bounded waits on
in-flight workers — a wedged worker is abandoned, never joined forever)
and re-raises, so a lane-guard fallback reruns serially on CPU against
quiesced workers. The ``compact.pipeline`` fail point fires in every
pool task for chaos coverage.

Counters (process registry -> /metrics, perf-counters*, collector):
  compact.pipeline.depth                                  gauge
  compact.pipeline.overlap_us / stall_us                  percentile
  compact.pipeline.prefetch_count / drain_count           rate
Per-range overlap additionally lands in the stage-span ring buffer as
``pipeline.overlap`` events (visible in /compact/trace and session
summaries -> bench ``detail.trace``).
"""

import os
import threading
import time

from ..runtime import lockrank
from ..runtime.fail_points import inject as _inject
from ..runtime.perf_counters import counters
from ..runtime.tasking import ThreadPool
from ..runtime.tracing import COMPACT_TRACER as _TRACE

_DEPTH_ENV = "PEGASUS_COMPACT_PIPELINE_DEPTH"
_DEFAULT_DEPTH = 2


def pipeline_depth() -> int:
    """The bounded lookahead (read per call so tests can flip it): depth
    N keeps at most N ranges in flight — 2 = classic double buffering,
    1 = serial (the pipeline disengages)."""
    v = os.environ.get(_DEPTH_ENV)
    try:
        d = int(v) if v not in (None, "") else _DEFAULT_DEPTH
    except ValueError:
        d = _DEFAULT_DEPTH
    return max(1, d)


_POOL = None     #: guarded_by _POOL_LOCK
_IO_POOL = None  #: guarded_by _POOL_LOCK
_COMPILE_POOL = None  #: guarded_by _POOL_LOCK
_POOL_LOCK = lockrank.named_lock("pipeline.pool_global")


def pipeline_pool() -> ThreadPool:
    """The process-wide host-side stage pool shared by the blockwise
    pipeline, the batched prefetch and the async device primes. Fixed
    size (not depth-derived: the pool is created once; deeper configured
    pipelines share workers and queue, which bounds concurrency without
    silently capping correctness). Stages here may touch the DEVICE, so
    a wedge can occupy a worker — never put work a drain must wait on
    here (that is what install_pool is for)."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPool("THREAD_POOL_COMPACT_PIPELINE",
                               worker_count=4)
        return _POOL


def install_pool() -> ThreadPool:
    """The engine's deferred-install pool: DISK-ONLY jobs (write_sst,
    manifest, unlinks) that drains wait on. Kept separate from
    pipeline_pool so wedged device work (primes, prefetch stages) can
    never starve an install job and hang flush/compact/close."""
    global _IO_POOL
    with _POOL_LOCK:
        if _IO_POOL is None:
            _IO_POOL = ThreadPool("THREAD_POOL_COMPACT_INSTALL",
                                  worker_count=2)
        return _IO_POOL


def compile_pool() -> ThreadPool:
    """Where kernels compile when a guarded call found them cold
    (ops/kernel.py): HOST-ONLY work, minutes long for a merge network on
    a TPU, so it gets its own workers — a compile must never occupy a
    prime/prefetch worker, nor wait behind a wedged one. Two workers:
    compiles of different programs overlap (two merge shapes in two
    threads took 47 s wall together on a v5e, PERF.md section 5), and a
    minutes-long merge compile cannot hold up the seconds-long read
    kernels for long."""
    global _COMPILE_POOL
    with _POOL_LOCK:
        if _COMPILE_POOL is None:
            _COMPILE_POOL = ThreadPool("THREAD_POOL_KERNEL_COMPILE",
                                       worker_count=2)
        return _COMPILE_POOL


class PipelineFuture:
    """Result slot for one pool-side stage; records its execution window
    so overlap against device dispatch windows is computable."""

    __slots__ = ("_ev", "value", "error", "started", "ended")

    def __init__(self):
        self._ev = threading.Event()
        self.value = None
        self.error = None
        self.started = 0.0
        self.ended = 0.0

    def done(self) -> bool:
        return self._ev.is_set()

    def wait(self, timeout=None) -> bool:
        return self._ev.wait(timeout)

    def result(self):
        self._ev.wait()
        if self.error is not None:
            raise self.error
        return self.value

    def duration_s(self) -> float:
        return max(0.0, self.ended - self.started)


def submit(fn, *args, pool: ThreadPool = None):
    """Run ``fn(*args)`` on the pipeline pool (or an explicit pool) ->
    PipelineFuture. The worker adopts the submitting thread's trace
    sessions AND its active job context for the task (then restores its
    own: pool workers are reused, and a stale adopted session/job would
    aggregate later unrelated spans into a closed run — the job adopt is
    what lets a deferred install's hop land in the compaction job that
    queued it, ISSUE 16)."""
    from ..runtime.job_trace import JOB_TRACER

    fut = PipelineFuture()
    sessions = _TRACE.propagate_sessions()
    job_id = JOB_TRACER.current()

    def run():
        prev = _TRACE.propagate_sessions()
        _TRACE.adopt_sessions(sessions)
        fut.started = time.perf_counter()
        try:
            with JOB_TRACER.adopt(job_id):
                _inject("compact.pipeline")
                fut.value = fn(*args)
        except BaseException as e:  # noqa: BLE001 - crosses the thread boundary
            fut.error = e
        finally:
            fut.ended = time.perf_counter()
            _TRACE.adopt_sessions(prev)
            fut._ev.set()

    (pool or pipeline_pool()).enqueue(run)
    return fut


def submit_install(fn, *args):
    """submit() onto the disk-only install pool (see install_pool)."""
    return submit(fn, *args, pool=install_pool())


def _fut_interval(f):
    """(start, end) of a finished worker future; None if it never ran or
    is still running (a timed-out, abandoned prefetch)."""
    if f is None or f.started == 0.0 or f.ended == 0.0:
        return None
    return (f.started, f.ended)


def _overlap_len(interval, others) -> float:
    """Seconds of ``interval`` during which at least one of the other
    intervals was also executing — summed per other (two concurrent
    overlappers count twice: both are real work hidden behind this one)."""
    t0, t1 = interval
    return sum(max(0.0, min(t1, e) - max(t0, s)) for s, e in others)


class CompactPipeline:
    """One bounded pipelined run over a list of work items. Create one
    instance per run — all state is local, so an abandoned (deadline-
    exceeded) run can never corrupt a later one."""

    def __init__(self, depth: int = None, drain_timeout_s: float = 5.0,
                 prefetch_timeout_s: float = None):
        self.depth = pipeline_depth() if depth is None else max(1, depth)
        self.drain_timeout_s = drain_timeout_s
        # None = wait forever for a prefetch (callers whose WHOLE map runs
        # under a lane guard, which deadline-abandons the stalled thread).
        # A guard-less caller (batched compaction) sets a bound instead:
        # on timeout the wedged worker is abandoned and dispatch receives
        # a TimeoutError MARKER in place of the prefetched value, so its
        # own per-item guard can redo the work inline with fallback.
        self.prefetch_timeout_s = prefetch_timeout_s
        self.stall_s = 0.0
        self.overlap_s = 0.0
        self.drains = 0

    def map(self, items, prefetch, dispatch, finish=None) -> list:
        """For each item i: ``prefetch(item)`` on a pool worker (bounded
        lookahead = depth-1), ``dispatch(i, prefetched)`` in the calling
        thread, ``finish(i, dispatched)`` on a pool worker (at most
        ``depth`` unfinished). Returns the finish (or dispatch) results
        in item order. Any stage error drains in-flight workers (bounded)
        and re-raises."""
        n = len(items)
        counters.number("compact.pipeline.depth").set(self.depth)
        if self.depth <= 1 or n <= 1:
            out = []
            for i, item in enumerate(items):
                d = dispatch(i, prefetch(item))
                out.append(finish(i, d) if finish is not None else d)
            return out
        lookahead = self.depth - 1
        pref = [None] * n
        fin = [None] * n
        results = [None] * n
        windows = []
        t_start = time.perf_counter()
        try:
            for i in range(n):
                for j in range(i, min(n, i + lookahead + 1)):
                    if pref[j] is None:
                        pref[j] = submit(prefetch, items[j])
                        counters.rate(
                            "compact.pipeline.prefetch_count").increment()
                p = self._take(pref[i])
                t0 = time.perf_counter()
                d = dispatch(i, p)
                windows.append((t0, time.perf_counter()))
                if finish is None:
                    results[i] = d
                    continue
                k = i - self.depth
                if k >= 0:
                    self._wait(fin[k])
                fin[i] = submit(finish, i, d)
            if finish is not None:
                for i in range(n):
                    self._wait(fin[i])
                    results[i] = fin[i].result()
        except BaseException:
            self._drain(pref + fin)
            self.drains += 1
            counters.rate("compact.pipeline.drain_count").increment()
            raise
        self._account(windows, pref, fin, time.perf_counter() - t_start)
        return results

    def _wait(self, fut, timeout: float = None) -> None:
        if fut is None or fut.done():
            return
        t0 = time.perf_counter()
        # the open span makes a stalled pipeline attributable: a wedged
        # prefetch worker shows up as `pipeline.stall` in the lane
        # guard's abandon message and the watchdog's wedged_at_stage
        with _TRACE.span("pipeline.stall"):
            fut.wait(timeout)
        self.stall_s += time.perf_counter() - t0

    def _take(self, fut):
        """Pick a prefetch result up, bounded by prefetch_timeout_s: a
        timed-out worker is abandoned and a TimeoutError marker takes the
        value's place (never raised here — the dispatch stage decides)."""
        self._wait(fut, self.prefetch_timeout_s)
        if not fut.done():
            return TimeoutError(
                f"pipeline prefetch exceeded {self.prefetch_timeout_s:.1f}s;"
                " worker abandoned")
        return fut.result()

    def _drain(self, futures) -> None:
        """Quiesce in-flight workers before a serial rerun: bounded wait
        per future — a wedged worker is abandoned (its pool thread frees
        itself whenever the wedge clears), never joined forever."""
        deadline = time.monotonic() + self.drain_timeout_s
        for f in futures:
            if f is None or f.done():
                continue
            f.wait(max(0.0, deadline - time.monotonic()))

    def _account(self, windows, pref, fin, wall_s) -> None:
        futures = pref + fin
        stage_s = wall_s - self.stall_s  # caller-thread time in stages
        stage_s += sum(f.duration_s() for f in futures if f is not None)
        self.overlap_s = max(0.0, stage_s - wall_s)
        counters.percentile("compact.pipeline.overlap_us").set(
            int(self.overlap_s * 1e6))
        counters.percentile("compact.pipeline.stall_us").set(
            int(self.stall_s * 1e6))
        # per-range overlap events: the seconds range i's WORKER stages
        # (its prefetch + finish) executed concurrently with any OTHER
        # work — device dispatch windows or other ranges' workers. This
        # is the host time the pipeline actually hid for that range.
        all_iv = {id(f): _fut_interval(f) for f in futures if f is not None}
        for i in range(len(pref)):
            own = [f for f in (pref[i], fin[i] if i < len(fin) else None)
                   if f is not None and _fut_interval(f) is not None]
            if not own:
                continue
            own_ids = {id(f) for f in own}
            others = list(windows) + [iv for fid, iv in all_iv.items()
                                      if iv is not None
                                      and fid not in own_ids]
            ov = sum(_overlap_len(_fut_interval(f), others) for f in own)
            if ov > 0.0:
                _TRACE.event("pipeline.overlap", ov)
