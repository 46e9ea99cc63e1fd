"""Stage-span tracing for the compaction pipeline.

The RPC layer already has a toollet tracer (runtime/toollets.py), but a
device wedge ("tpu lane exceeded 360s") happens BELOW the RPC layer, inside
the compaction pipeline: device init, host pack, H2D upload, the sort/merge
kernel, or the survivor gather. This module is the in-pipeline probe that
LUDA/RESYSTANCE-style offload perf work needs before any kernel tuning is
trustworthy: nestable stage spans with wall time, record and byte counts,

  - ring-buffered like the RPC tracer: the recent spans dump through the
    `compact-trace-dump` remote command and the `/compact/trace` HTTP
    route (runtime/service_app.py);
  - exported into the process-wide perf-counter registry under
    `compact.stage.<name>.*` (rate counters for span/record/byte
    throughput, a percentile counter for duration), so `/metrics`,
    `perf-counters*`, and the collector all read ONE registry;
  - visible while still OPEN (open_stages / innermost_open): the
    device-health watchdog (ops/device_watchdog.py) reads the live span
    stack to attribute a wedge to the exact stage that never returned.

Stage names used by the pipeline: compact > pack / h2d / device / gather,
plus sst_write at the engine write-out. Spans nest (depth is recorded);
a stage entered recursively (blockwise range decomposition re-enters
`compact`) shows up once per entry, so session sums for such stages count
the nested time once per level — read `calls` alongside `s`.

A TraceSession aggregates every span closed in its thread while active;
the manual-compact service records per-stage breakdowns from it
(`manual_compact`'s `stats["trace"]`).
"""

import collections
import heapq
import os
import random
import sys
import threading
import time
from contextlib import contextmanager

from .perf_counters import counters

# ============================================================ stage spine
#
# What the two tracers below share (ISSUE 27). Every span either of them
# closes adds to three monotone `number` counters, `stage.<name>.n`
# (closes), `.us` (duration) and `.self_us` (duration minus the spans
# closed inside it IN THE SAME THREAD), so any stage can be differenced
# over a window through `perf-counters-by-prefix stage.` or /metrics —
# the rings and ledgers below keep individual spans, these keep the sums.
# One thread-local stack of open spans serves both tracers, so a
# `read.device` stage span inside an `engine.get` request span subtracts
# from it; a span closed by ANOTHER thread (a lane-guard worker, the
# prepare fan-out) never does: its caller's self time is then the wait.
# While a span is open it also holds a `jax.profiler.TraceAnnotation`
# named `pegasus:<name>`, so a profile of the chip-holding process shows
# the program's stages on /host:CPU beside the device's lines. The class
# is taken from sys.modules: a process that has not imported jax (the
# clients, the shell) never imports it for this. Stage spans (a few
# hundred a second at most) always hold one; request spans (some twenty
# an update: 13,000 a second on a busy node) hold one only between the
# `profile-start` and `profile-stop` remote commands, which call
# annotate_requests() — a trace somebody else takes of this process is
# not swollen thirtyfold by them.

_SPINE = threading.local()   # .stack: [[name, child_us], ...] innermost LAST
_STAGES = {}    # span name -> (lock, n, us, self_us, "pegasus:<name>", name)
_STAGES_LOCK = threading.Lock()
_ANNOTATION = None           # jax.profiler.TraceAnnotation, once jax is loaded
_ANNOTATE_REQUESTS = False   # see annotate_requests()


def annotate_requests(on: bool) -> None:
    """Whether RequestTracer spans hold a profiler annotation while open
    (runtime/remote_command.py profile-start / profile-stop)."""
    global _ANNOTATE_REQUESTS
    _ANNOTATE_REQUESTS = bool(on)


def _stage(name: str) -> tuple:
    """The three counters of one span name, resolved once and kept: a
    span close formats no string and takes no registry lock."""
    st = _STAGES.get(name)
    if st is None:
        with _STAGES_LOCK:
            st = _STAGES.get(name)
            if st is None:
                base = "stage." + name
                st = _STAGES[name] = (threading.Lock(),
                                      counters.number(base + ".n"),
                                      counters.number(base + ".us"),
                                      counters.number(base + ".self_us"),
                                      "pegasus:" + name, name)
    return st


def _find_annotation():
    global _ANNOTATION
    cls = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                  "TraceAnnotation", None)
    if cls is not None:
        _ANNOTATION = cls
    return cls


def _add_totals(st: tuple, dur_us: int, child_us: int = 0) -> None:
    """The one place a closed span reaches the `stage.` counters: three
    adds under the stage's ONE lock (nothing else writes these counters,
    so their own locks are not taken: a third of the cost on a path that
    closes some thirty spans an update)."""
    with st[0]:
        st[1]._value += 1
        st[2]._value += dur_us
        st[3]._value += dur_us - child_us if dur_us > child_us else 0


def _open(st: tuple, annotate: bool = True) -> tuple:
    """Open the span of stage `st` in this thread -> the token _close
    takes."""
    try:
        stack = _SPINE.stack
    except AttributeError:
        stack = _SPINE.stack = []
    parent = stack[-1][0] if stack else ""
    stack.append([st[5], 0])
    cls = (_ANNOTATION or _find_annotation()) if annotate else None
    ann = None
    if cls is not None:
        ann = cls(st[4])
        ann.__enter__()
    return st, stack, parent, ann, time.perf_counter()


def _close(tok: tuple) -> tuple:
    """-> (duration_us, the enclosing span's name or "")."""
    st, stack, parent, ann, t0 = tok
    dur_us = int((time.perf_counter() - t0) * 1e6)
    if ann is not None:
        ann.__exit__(None, None, None)
    child_us = stack.pop()[1]
    if stack:
        stack[-1][1] += dur_us
    _add_totals(st, dur_us, child_us)
    return dur_us, parent


class TraceSession:
    """Per-stage aggregate of the spans closed (in the owning thread)
    while the session was active: stage -> {s, calls, records, bytes}."""

    def __init__(self):
        self.stages = {}
        self.started_at = time.time()

    def _add(self, stage: str, dur_s: float, records: int, nbytes: int,
             cpu_s: float = 0.0):
        agg = self.stages.setdefault(
            stage, {"s": 0.0, "cpu_s": 0.0, "calls": 0, "records": 0,
                    "bytes": 0})
        agg["s"] += dur_s
        agg["cpu_s"] += cpu_s
        agg["calls"] += 1
        agg["records"] += records
        agg["bytes"] += nbytes

    def summary(self) -> dict:
        """JSON-ready copy with rounded wall times (stage order = first
        close order, which for a straight-line pipeline is stage order).
        `cpu_s` is the PROCESS cpu-time delta across the span — host
        contention is diagnosable from the artifact: cpu_s >> s means
        other threads worked in parallel under the span; s >> cpu_s with
        a high loadavg means the host starved the stage."""
        return {k: dict(v, s=round(v["s"], 6), cpu_s=round(v["cpu_s"], 6))
                for k, v in self.stages.items()}


class StageTracer:
    def __init__(self, capacity: int = 4096, prefix: str = "compact"):
        self.prefix = prefix
        self._lock = threading.Lock()
        self._spans = collections.deque(maxlen=capacity)
        self._local = threading.local()
        # thread ident -> [(stage, started_wall_ts), ...] innermost LAST;
        # shared (not thread-local) so the watchdog thread can read which
        # stage another thread is currently stuck in
        self._open = {}
        self._exports = {}   # stage -> its four registry counters

    # ----------------------------------------------------------- span API

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _session_list(self) -> list:
        s = getattr(self._local, "sessions", None)
        if s is None:
            s = self._local.sessions = []
        return s

    @contextmanager
    def span(self, stage: str, records: int = 0, nbytes: int = 0):
        """Time one pipeline stage. Yields a mutable {records, bytes} box
        so counts discovered mid-span (e.g. survivor count) can be added
        before the span closes."""
        stack = self._stack()
        depth = len(stack)
        stack.append(stage)
        tid = threading.get_ident()
        with self._lock:
            self._open.setdefault(tid, []).append((stage, time.time()))
        box = {"records": records, "bytes": nbytes}
        c0 = time.process_time()
        tok = _open(_stage(stage))
        try:
            yield box
        finally:
            dur_s = _close(tok)[0] / 1e6
            # process (not thread) cpu time: includes concurrent threads'
            # work under the span — exactly what makes host contention
            # attributable from a recorded trace (see TraceSession.summary)
            cpu_s = time.process_time() - c0
            stack.pop()
            with self._lock:
                open_list = self._open.get(tid)
                if open_list:
                    open_list.pop()
                    if not open_list:
                        self._open.pop(tid, None)
                self._spans.append((time.time(), depth, stage, dur_s,
                                    box["records"], box["bytes"], cpu_s))
            self._export(stage, dur_s, box["records"], box["bytes"])
            for sess in self._session_list():
                sess._add(stage, dur_s, box["records"], box["bytes"], cpu_s)

    def event(self, stage: str, dur_s: float, records: int = 0,
              nbytes: int = 0) -> None:
        """Record a synthetic closed span — a duration computed after the
        fact rather than timed in a context (the pipeline's per-range
        overlap intervals). Lands in the ring buffer, the counter
        registry and this thread's active sessions exactly like a span."""
        with self._lock:
            self._spans.append((time.time(), 0, stage, dur_s, records,
                                nbytes, 0.0))
        # an interval computed after the fact may overlap open spans: it
        # is all self time and subtracts from nothing
        _add_totals(_stage(stage), int(dur_s * 1e6))
        self._export(stage, dur_s, records, nbytes)
        for sess in self._session_list():
            sess._add(stage, dur_s, records, nbytes)

    def _export(self, stage, dur_s, records, nbytes):
        """`<prefix>.stage.<name>.*` (README; the scheduler's autotune
        reads them), through counters resolved once per stage."""
        ex = self._exports.get(stage)
        if ex is None:
            base = f"{self.prefix}.stage.{stage}"
            ex = self._exports[stage] = (
                counters.rate(f"{base}.count"),
                counters.percentile(f"{base}.duration_us"),
                counters.rate(f"{base}.records"),
                counters.rate(f"{base}.bytes"))
        ex[0].increment()
        ex[1].set(int(dur_s * 1e6))
        if records:
            ex[2].increment(records)
        if nbytes:
            ex[3].increment(nbytes)

    @contextmanager
    def session(self):
        """Aggregate the spans this thread closes while the context is
        active (sessions nest; each gets its own aggregate)."""
        sess = TraceSession()
        sessions = self._session_list()
        sessions.append(sess)
        try:
            yield sess
        finally:
            sessions.remove(sess)

    # ------------------------------------------- cross-thread session hand-off

    def propagate_sessions(self) -> list:
        """Snapshot this thread's active session list so a WORKER thread
        (the lane guard runs device calls under a deadline in one) can
        adopt it — spans the worker closes then still aggregate into the
        caller's sessions (manual_compact's per-stage trace must survive
        the guard's thread hop). The caller normally blocks on the worker;
        an ABANDONED (deadline-exceeded) worker may close spans late and
        race the caller's own adds — TraceSession increments are
        GIL-atomic, so a wedge can at worst slightly inflate a summary,
        never corrupt it."""
        return list(self._session_list())

    def adopt_sessions(self, sessions: list) -> None:
        """Install a propagated session snapshot in THIS thread."""
        self._local.sessions = list(sessions)

    # ----------------------------------------------- live-state inspection

    def open_stages(self) -> dict:
        """thread ident -> [stage, ...] (outermost first) for every thread
        with an open span — what the watchdog snapshots on a failed probe."""
        with self._lock:
            return {tid: [s for s, _ in st] for tid, st in self._open.items()}

    def innermost_open(self):
        """(stage, started_wall_ts) of the open span most likely wedged:
        the innermost span of whichever stack has been sitting in its
        innermost stage the LONGEST. None when nothing is open."""
        best = None
        with self._lock:
            for st in self._open.values():
                if not st:
                    continue
                stage, t0 = st[-1]
                if best is None or t0 < best[1]:
                    best = (stage, t0)
        return best

    # ------------------------------------------------------ ring-buffer IO

    def trace(self, last: int = 100) -> list:
        """The most recent closed spans as JSON-ready dicts (close order:
        children close before their parents)."""
        with self._lock:
            spans = list(self._spans)[-last:]
        return [{"ts": ts, "depth": depth, "stage": stage,
                 "duration_us": int(dur_s * 1e6),
                 "cpu_us": int(cpu_s * 1e6),
                 "records": records, "bytes": nbytes}
                for ts, depth, stage, dur_s, records, nbytes, cpu_s in spans]

    def dump(self, last: int = 100) -> str:
        rows = self.trace(last)
        return "\n".join(
            f"{r['ts']:.6f} {'  ' * r['depth']}{r['stage']} "
            f"{r['duration_us']}us records={r['records']} bytes={r['bytes']}"
            for r in rows) or "no spans"


# process-wide tracer, like the global counter registry: every pipeline
# layer (ops, engine, parallel, bench) threads spans through this instance
COMPACT_TRACER = StageTracer()


# ======================================================== request tracing
#
# Where the StageTracer above times the compaction pipeline (a background
# job), the RequestTracer times the SERVING path: one trace per client
# request, its id carried in the RPC header (rpc/transport.py RpcHeader
# trace_id/trace_sampled) from client/client.py through the replica
# serverlet, the PacificA prepare/commit round, the private-log append and
# the engine apply. Spans are recorded at close time (children before
# parents, like StageTracer) into one per-trace record.
#
# Retention is two-tier:
#   - a sampled ring buffer of completed traces (every `sample_every`-th
#     trace; default every trace; PEGASUS_TRACE_SAMPLE_EVERY=0 turns
#     request traces off: no id on the wire, no record, only the stage
#     totals), served by GET /requests/trace and the `request-trace-dump`
#     remote command;
#   - a slow-request ledger: ANY trace whose end-to-end duration reaches
#     `slow_threshold_us` keeps its full stage timeline regardless of
#     sampling: the newest 256, and the slowest 32 since start, which a
#     busy node's newest-256 would overwrite in seconds — served by GET
#     /requests/trace?slow=1 and the `slow-requests` remote command
#     (slowest first). A slow put is attributable to the
#     client hop, the RPC layer, the quorum round or the engine without
#     reproducing it.
#
# Cross-process semantics: each process records the spans IT closes. The
# originating client owns the trace (root_local) and finalizes it; a
# server process that received the context over the wire finalizes its own
# partial view when its last concurrently-open handler for that trace
# returns. In a onebox (everything in one process, one global
# REQUEST_TRACER) the two sides share one record, so a single client put
# yields a single trace holding client, rpc, replication, plog and engine
# spans — the acceptance shape tests/test_request_tracing.py pins.


class TraceContext:
    """What travels in the RPC header: trace identity + sampling flag.
    `remote` marks a context that arrived over the wire (this process does
    not own the trace root)."""

    __slots__ = ("trace_id", "sampled", "remote")

    def __init__(self, trace_id: int, sampled: bool = True,
                 remote: bool = False):
        self.trace_id = trace_id
        self.sampled = sampled
        self.remote = remote


_THREADS_CTX = object()   # "the context installed in this thread"


class _RequestSpan:
    """One RequestTracer span: `with` yields its mutable attr dict. A
    wait inside a retry loop, which cannot be a `with` block, calls
    begin() where it first parks and end() where it stops (both may be
    called again: the wait is ONE span however often the loop turns)."""

    __slots__ = ("tr", "name", "attrs", "ctx", "st", "e", "depth", "ts",
                 "tok")

    def __init__(self, tr, name: str, attrs: dict, ctx=_THREADS_CTX):
        self.tr, self.name, self.attrs, self.ctx = tr, name, attrs, ctx
        # resolved here, not at the first close: a wait that never parked
        # still publishes its (zero) totals
        self.st = _stage(name)
        self.tok = None

    def begin(self) -> None:
        if self.tok is None:
            self.__enter__()

    def end(self) -> None:
        if self.tok is not None:
            self.__exit__()
            self.tok = None

    def __enter__(self) -> dict:
        local = self.tr._local
        ctx = self.ctx
        if ctx is _THREADS_CTX:
            ctx = getattr(local, "ctx", None)
        # a dict read is GIL-atomic: no tracer lock on the span path
        e = self.tr._active.get(ctx.trace_id) if ctx is not None else None
        self.e = e
        if e is not None:
            self.depth = getattr(local, "depth", 0)
            local.depth = self.depth + 1
            self.ts = time.time()
        self.tok = _open(self.st, _ANNOTATE_REQUESTS)
        return self.attrs

    def __exit__(self, *exc) -> bool:
        dur_us, parent = _close(self.tok)
        e = self.e
        if e is not None:
            self.tr._local.depth = self.depth
            rec = {"name": self.name, "ts": self.ts, "depth": self.depth,
                   "parent": parent, "duration_us": dur_us}
            rec.update(self.attrs)
            if len(e["spans"]) < self.tr.MAX_SPANS:
                e["spans"].append(rec)
        return False


class RequestTracer:
    MAX_ACTIVE = 4096       # leaked/abandoned trace guard
    MAX_SPANS = 512         # per-trace span cap (runaway scan sessions)
    WORST = 32              # slowest traces since start, kept for good

    def __init__(self, capacity: int = 512, slow_capacity: int = 256):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ring = collections.deque(maxlen=capacity)
        self._slow = collections.deque(maxlen=slow_capacity)
        # min-heap of (duration_us, seq, trace): what is being hunted
        # outlives the newest-256 ring (a busy node overwrites that in
        # seconds)
        self._worst = []
        self._worst_seq = 0
        self._active = {}   # trace_id -> open trace record
        self.slow_threshold_us = int(
            os.environ.get("PEGASUS_SLOW_REQUEST_US", "50000"))
        # 0 = request traces off: no id on the wire, no per-trace record;
        # the stage totals above stay
        self.sample_every = max(0, int(
            os.environ.get("PEGASUS_TRACE_SAMPLE_EVERY", "1")))
        self._seq = 0

    # ------------------------------------------------------------ context

    def current(self):
        """The TraceContext active in this thread, or None."""
        return getattr(self._local, "ctx", None)

    def _entry(self, trace_id: int, op: str, root_local: bool) -> dict:
        with self._lock:
            e = self._active.get(trace_id)
            if e is None:
                while len(self._active) >= self.MAX_ACTIVE:
                    self._active.pop(next(iter(self._active)))
                e = {"trace_id": trace_id, "op": op, "started": time.time(),
                     "spans": [], "root_local": root_local, "refs": 0}
                self._active[trace_id] = e
            return e

    @contextmanager
    def root(self, op: str):
        """Begin a trace in this thread (the CLIENT side of a request).
        Records a `client.<op>` span and finalizes the trace at exit.
        Nested client ops inside an active trace (e.g. copy_data's reads
        feeding writes) record plain spans instead of new traces; so does
        every op when sample_every is 0 (yields None: nothing to carry)."""
        prev = self.current()
        if prev is not None or not self.sample_every:
            with self.span(f"client.{op}"):
                yield prev
            return
        with self._lock:
            self._seq += 1
            sampled = (self._seq % self.sample_every) == 0
        ctx = TraceContext(random.getrandbits(63) | 1, sampled)
        e = self._entry(ctx.trace_id, op, root_local=True)
        self._local.ctx = ctx
        t0 = time.perf_counter()
        try:
            with self.span(f"client.{op}"):
                yield ctx
        finally:
            self._local.ctx = None
            self._finalize(e, int((time.perf_counter() - t0) * 1e6),
                           ctx.sampled)

    @contextmanager
    def serve(self, ctx, op: str):
        """Install a wire-propagated context for a SERVER-side handler and
        record the `rpc.server.<op>` span. When this process does not own
        the trace root, the trace's local view finalizes once its last
        open handler returns. ctx None (a frame without a trace id) still
        times the span for the stage totals."""
        if ctx is None:
            with self.span(f"rpc.server.{op}"):
                yield None
            return
        prev = self.current()
        e = self._entry(ctx.trace_id, op, root_local=False)
        with self._lock:
            e["refs"] += 1
        self._local.ctx = ctx
        t0 = time.perf_counter()
        try:
            with self.span(f"rpc.server.{op}"):
                yield ctx
        finally:
            self._local.ctx = prev
            with self._lock:
                e["refs"] -= 1
                done = e["refs"] == 0 and not e["root_local"]
            if done:
                self._finalize(e, int((time.perf_counter() - t0) * 1e6),
                               ctx.sampled)

    @contextmanager
    def adopt(self, ctx):
        """Install an existing context in THIS thread for a worker-pool
        hop (the parallel prepare fan-out runs _send_prepare_window on pool
        threads) — spans the worker closes join the owner's trace. No
        finalize: the owning thread's root/serve does that, and it blocks
        on the workers before closing, so the trace stays active. ctx
        may be None (untraced caller) — then this is a no-op."""
        if ctx is None:
            yield None
            return
        prev = getattr(self._local, "ctx", None)
        self._local.ctx = ctx
        try:
            yield ctx
        finally:
            self._local.ctx = prev

    def span(self, name: str, **attrs) -> _RequestSpan:
        """Time one stage of the serving path: always into the stage
        totals, and into the active trace's record when this thread has a
        context. `with` yields the mutable attr dict so counts discovered
        mid-span can be added before it closes."""
        return _RequestSpan(self, name, attrs)

    def span_in(self, ctx, name: str, **attrs) -> _RequestSpan:
        """span() for a context this thread no longer has installed (the
        reply write after serve() has closed); ctx may be None."""
        return _RequestSpan(self, name, attrs, ctx)

    def event(self, name: str, dur_us: int, **attrs) -> None:
        """A closed span measured after the fact (a frame's wait for a
        pool thread ends where its handler starts): totals, and a record
        in the active trace whose `ts` is the interval's start."""
        _add_totals(_stage(name), dur_us)
        ctx = getattr(self._local, "ctx", None)
        e = self._active.get(ctx.trace_id) if ctx is not None else None
        if e is not None and len(e["spans"]) < self.MAX_SPANS:
            stack = getattr(_SPINE, "stack", None)
            rec = {"name": name, "ts": time.time() - dur_us / 1e6,
                   "depth": getattr(self._local, "depth", 0),
                   "parent": stack[-1][0] if stack else "",
                   "duration_us": dur_us}
            rec.update(attrs)
            e["spans"].append(rec)

    # ---------------------------------------------------------- retention

    def _finalize(self, e: dict, dur_us: int, sampled: bool) -> None:
        trace = {"trace_id": format(e["trace_id"], "016x"), "op": e["op"],
                 "ts": e["started"], "duration_us": dur_us,
                 "spans": e["spans"]}
        slow = dur_us >= self.slow_threshold_us
        with self._lock:
            self._active.pop(e["trace_id"], None)
            if slow:
                self._slow.append(trace)
                self._worst_seq += 1   # ties never compare the dicts
                item = (dur_us, self._worst_seq, trace)
                if len(self._worst) < self.WORST:
                    heapq.heappush(self._worst, item)
                elif dur_us > self._worst[0][0]:
                    heapq.heapreplace(self._worst, item)
            if sampled:
                self._ring.append(trace)
        counters.rate("request.trace.completed_count").increment()
        counters.percentile("request.trace.duration_us").set(dur_us)
        if slow:
            counters.rate("request.trace.slow_count").increment()

    def trace(self, last: int = 50) -> list:
        """The most recent sampled completed traces, JSON-ready."""
        with self._lock:
            return list(self._ring)[-last:]

    def slow_requests(self, last: int = 50) -> list:
        """The slow-request ledger: full stage timelines of requests that
        crossed slow_threshold_us — the WORST slowest since start first
        (worst first), then the newest `last` of the rest."""
        with self._lock:
            worst = [t for _, _, t in sorted(self._worst, reverse=True)]
            recent = list(self._slow)[-last:]
        kept = {id(t) for t in worst}
        return worst + [t for t in recent if id(t) not in kept]

    def find(self, trace_id: str):
        """Look one completed trace up by hex id (ledger first: slow
        traces are the ones being hunted)."""
        with self._lock:
            for t in ([t for _, _, t in self._worst] + list(self._slow)
                      + list(self._ring)):
                if t["trace_id"] == trace_id:
                    return t
        return None


# process-wide request tracer: client, transport, replication and engine
# all record into this instance (one process = one local trace view)
REQUEST_TRACER = RequestTracer()
