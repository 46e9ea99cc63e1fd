"""Lane guard: the ONE failure policy for every device-backed compaction.

The watchdog (ops/device_watchdog.py) can name the stage a device call
wedged in, but without this module the server still hung on it forever,
and the engine handled device failure with scattered ad-hoc ``except
Exception: degrade`` branches. Both compaction backends guarantee
byte-identical output (tests/test_compact_ops.py), so the TPU lane is
an *optimization* that must never be an availability risk — LUDA
(PAPERS.md) makes the same argument for GPU compaction offload. This
module centralizes that contract:

  1. DEADLINE — a device call runs in a worker thread under an in-process
     deadline derived from the watchdog heartbeat; exceeding it abandons
     the worker (python cannot kill a thread blocked inside the backend)
     and reports the wedged stage from the worker's open span stack.
  2. RETRY — transient device errors retry with bounded exponential
     backoff (deterministic, no jitter). A deadline abandon does NOT
     retry: the lane is wedged, and retrying would stack more abandoned
     device threads against one wedged device.
  3. FALLBACK — exhausted retries (or a wedge) rerun the compaction on
     the cpu backend, byte-identical by contract.
  4. CIRCUIT BREAKER — after `breaker_threshold` CONSECUTIVE device
     failures/wedges every guarded compaction routes straight to cpu for
     `breaker_cooldown_s`; when the cooldown lapses the breaker re-probes
     the device via the watchdog (half-open) and only a passing probe
     closes it.
  5. COMPILE-BEHIND — a guarded call never compiles. A cold XLA:TPU
     compile of a merge network takes one to three minutes, longer than
     the deadline, and most guarded calls sit inside a write or read RPC.
     A kernel (ops/kernel.py DeviceKernel) that is not compiled yet
     starts compiling on the compile pool and raises KernelCompiling;
     that is not a device failure (no retry, no breaker, no failure
     total): the call is served by the fallback and counted in
     `compile_behind`, and the next call of that shape finds the program
     ready. A caller that asked for the device — a manual compaction
     (`with compile_wait():`), or a call with no fallback — instead waits
     for the compile on ITS thread, outside the deadline, for at most
     COMPILE_BOUND_S, and then re-attempts under a fresh deadline; a
     wait that runs out is counted in `compile_wait_timeouts`.

Call sites: ops/compact.py (single merge), ops/batched_compact.py (one
vmapped dispatch per shape group), parallel/sharded_compact.py (multi-chip
all_to_all merge). A caller that passes no fallback_fn gets the device's
result or the failure, never the cpu path under the device's name.

Counters (process registry -> /metrics, perf-counters*, collector):
  compact.lane.fallback_count / retry_count /
  compact.lane.deadline_abandon_count / breaker_trip_count /
  compact.lane.compile_behind_count                            rate
  compact.lane.breaker_open                                    gauge (0/1)

Monotonic totals (rate counters reset on read) live in state(), which
rides in the device-health remote command, /compact/trace and
query_compact_state.

Env knobs (read once at import for the process-wide LANE_GUARD):
  PEGASUS_LANE_DEADLINE_S / PEGASUS_LANE_MAX_RETRIES /
  PEGASUS_LANE_BREAKER_THRESHOLD / PEGASUS_LANE_BREAKER_COOLDOWN_S

Since ISSUE 7 there are TWO lanes sharing this policy class but nothing
else: the compaction lane (LANE_GUARD, counters `compact.lane.*`) and the
serving read lane (READ_LANE_GUARD, counters `read.lane.*`) guarding the
device point-lookup path (ops/device_lookup.py via engine/db.py
get_batch). Separate instances mean separate breakers: a wedged read
probe routes READS to the host walk without pushing compactions off the
device, and vice versa (test-enforced in tests/test_lane_guard.py).
Read-lane knobs: PEGASUS_READ_LANE_DEADLINE_S (default 30 — reads are
latency-sensitive; the host fallback is always available) /
PEGASUS_READ_LANE_MAX_RETRIES / PEGASUS_READ_LANE_BREAKER_THRESHOLD /
PEGASUS_READ_LANE_BREAKER_COOLDOWN_S.
"""

import contextlib
import os
import threading
import time
from dataclasses import dataclass

from . import events, lockrank
from .perf_counters import counters
from .tracing import COMPACT_TRACER


class _LaneWorker(threading.Thread):  #: untracked_ok abandoned-by-design deadline workers: a thread wedged inside the backend is never joined, so the tracked registry's join_all must not see it
    """Reusable deadline worker: the guard hands it one call at a time
    and waits with a timeout. On timeout the caller ABANDONS it (a
    thread blocked inside the backend cannot be killed) and the worker
    re-joins the guard's idle pool only after the stale call eventually
    finishes; a truly wedged worker simply never comes back, and the
    pool spawns a fresh one on demand. This keeps the per-call cost of a
    guarded attempt at an Event round-trip instead of a thread spawn —
    the read lane puts the guard on the serving hot path."""

    def __init__(self, guard):
        super().__init__(daemon=True, name=f"lane-{guard.metric_prefix}")
        self._guard = guard
        self._ready = threading.Event()
        self._job = None

    def submit(self, fn, box, done, sessions, job_id=None) -> None:
        self._job = (fn, box, done, sessions, job_id)
        self._ready.set()

    def run(self):
        from .job_trace import JOB_TRACER

        while True:
            self._ready.wait()
            self._ready.clear()
            fn, box, done, sessions, job_id = self._job
            self._job = None
            self._guard.tracer.adopt_sessions(sessions)
            try:
                with JOB_TRACER.adopt(job_id):
                    box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 - crosses the thread boundary
                box["error"] = e
            done.set()
            with self._guard._lock:
                self._guard._idle_workers.append(self)


# the longest any thread waits for one XLA compile (the largest measured
# on a v5e: 203 s for the 10M-record merge, PERF.md section 5)
COMPILE_BOUND_S = 900.0


class LaneError(RuntimeError):
    """Device lane failed and no fallback was provided."""


class LaneDeadlineExceeded(LaneError):
    """The device call outlived its deadline and was abandoned."""


class KernelCompiling(Exception):
    """Raised by a DeviceKernel called under a lane guard before its
    program is compiled (the compile is already running on the compile
    pool). Policy input, not a device error: see COMPILE-BEHIND above.
    `done` is the event the compile sets when it ends, either way."""

    def __init__(self, kernel: str, done: threading.Event):
        super().__init__(f"kernel {kernel} is still compiling")
        self.kernel = kernel
        self.done = done


def in_guarded_call() -> bool:
    """True on a lane worker thread, i.e. under some guard's deadline."""
    return isinstance(threading.current_thread(), _LaneWorker)


_CALLER = threading.local()


@contextlib.contextmanager
def compile_wait(seconds: float = COMPILE_BOUND_S):
    """Within this block the calling thread's guarded calls WAIT (outside
    their deadline, at most `seconds` per call) for a kernel that is still
    compiling instead of handing the call to the fallback. For work an
    operator asked the device to do — manual compaction, a bench — never
    for the write or read path."""
    prev = getattr(_CALLER, "compile_wait_s", None)
    _CALLER.compile_wait_s = seconds
    try:
        yield
    finally:
        _CALLER.compile_wait_s = prev


def _env_float(name, default):
    v = os.environ.get(name)
    return default if v in (None, "") else float(v)


def _env_int(name, default):
    v = os.environ.get(name)
    return default if v in (None, "") else int(v)


@dataclass
class LaneGuardConfig:
    # None = derive from the watchdog heartbeat at call time (see
    # LaneGuard.effective_deadline_s); <= 0 disables the deadline (the
    # device call runs inline in the caller's thread)
    deadline_s: float = None
    max_retries: int = 2
    backoff_base_s: float = 0.05
    backoff_max_s: float = 2.0
    breaker_threshold: int = 3
    breaker_cooldown_s: float = 30.0
    # how long a call WITH a fallback waits for a kernel that is still
    # compiling before the fallback serves it (COMPILE-BEHIND). 0 in
    # production: neither a write nor a read waits for the compiler.
    # tests/conftest.py raises it so the suite exercises the device path
    # on every first call; `with compile_wait():` overrides it per thread
    compile_wait_s: float = 0.0

    @classmethod
    def from_env(cls, env_prefix: str = "PEGASUS_LANE",
                 deadline_s: float = None, max_retries: int = 2,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0) -> "LaneGuardConfig":
        return cls(
            deadline_s=_env_float(f"{env_prefix}_DEADLINE_S", deadline_s),
            max_retries=_env_int(f"{env_prefix}_MAX_RETRIES", max_retries),
            breaker_threshold=_env_int(f"{env_prefix}_BREAKER_THRESHOLD",
                                       breaker_threshold),
            breaker_cooldown_s=_env_float(f"{env_prefix}_BREAKER_COOLDOWN_S",
                                          breaker_cooldown_s),
        )


class LaneGuard:
    def __init__(self, config: LaneGuardConfig = None, tracer=COMPACT_TRACER,
                 probe_fn=None, metric_prefix: str = "compact.lane"):
        self.config = config or LaneGuardConfig()
        self.tracer = tracer
        # counter namespace: "compact.lane" for the compaction lane,
        # "read.lane" for the serving read lane (see module docstring)
        self.metric_prefix = metric_prefix
        # injectable half-open probe (tests); default = the watchdog's
        # liveness round-trip, lazily bound to avoid a runtime->ops import
        # at module load
        self.probe_fn = probe_fn
        self._lock = lockrank.named_lock(f"laneguard.{metric_prefix}")
        # serializes the half-open re-probe: exactly one thread pays the
        # probe timeout against a possibly-wedged device; concurrent
        # callers keep routing to cpu meanwhile
        self._half_open_lock = lockrank.named_lock(
            f"laneguard.half_open.{metric_prefix}")
        # reusable deadline workers (LIFO)
        self._idle_workers = []  #: guarded_by self._lock
        self.fallback_count = 0  #: guarded_by self._lock
        self.retry_count = 0     #: guarded_by self._lock
        self.deadline_abandon_count = 0  #: guarded_by self._lock
        self.breaker_trip_count = 0      #: guarded_by self._lock
        self.device_failure_count = 0    #: guarded_by self._lock
        self.compile_behind_count = 0    #: guarded_by self._lock
        self.compile_wait_timeout_count = 0  #: guarded_by self._lock
        self._consec_failures = 0        #: guarded_by self._lock
        self._breaker_open_until = 0.0   # monotonic  #: guarded_by self._lock
        # {"op", "error", "stage", "ts"}
        self.last_failure = None   #: guarded_by self._lock
        # {"op", "reason", "ts"}
        self.last_fallback = None  #: guarded_by self._lock

    # ------------------------------------------------------------ plumbing

    @staticmethod
    def _watchdog():
        from ..ops.device_watchdog import WATCHDOG

        return WATCHDOG

    def _probe(self) -> bool:
        if self.probe_fn is not None:
            return bool(self.probe_fn())
        return self._watchdog().probe()

    def effective_deadline_s(self) -> float:
        """The in-process deadline, derived from the watchdog heartbeat
        when not configured: long enough that `fail_threshold` heartbeat
        cycles can independently flip wedged_at_stage first (attribution
        beats abandonment), floored generously so a long but healthy
        device call is never mistaken for a wedge (compilation is not
        part of it: a guarded call never compiles)."""
        if self.config.deadline_s is not None:
            return self.config.deadline_s
        wd = self._watchdog()
        return max(120.0, (wd.probe_timeout_s + wd.interval_s)
                   * (wd.fail_threshold + 2))

    # ------------------------------------------------------------- breaker

    def breaker_open(self, probe: bool = True) -> bool:
        """True while device work must be skipped. When the cooldown has
        lapsed this HALF-OPENS: one watchdog probe decides — pass closes
        the breaker, fail re-arms the full cooldown. Only ONE thread
        probes at a time (a probe against a wedged device blocks for its
        timeout); everyone else keeps routing to cpu meanwhile.

        probe=False is the passive check for paths that must never block
        on a device probe (the engine's HBM prime): an open breaker stays
        open to them until a guarded compaction's half-open probe passes.
        """
        with self._lock:
            if self._consec_failures < self.config.breaker_threshold:
                return False
            cooling = time.monotonic() < self._breaker_open_until
        if cooling or not probe:
            return True
        if not self._half_open_lock.acquire(blocking=False):
            return True  # someone else is probing right now
        try:
            with self._lock:  # re-check: the prior prober may have closed it
                if self._consec_failures < self.config.breaker_threshold:
                    return False
                if time.monotonic() < self._breaker_open_until:
                    return True
            if self._probe():
                with self._lock:
                    self._consec_failures = 0
                    self._breaker_open_until = 0.0
                counters.number(self.metric_prefix + ".breaker_open").set(0)
                events.emit("lane.breaker_close", lane=self.metric_prefix,
                            via="half_open_probe")
                return False
            with self._lock:
                self._breaker_open_until = (time.monotonic()
                                            + self.config.breaker_cooldown_s)
            return True
        finally:
            self._half_open_lock.release()

    def record_device_failure(self, op: str, error: str, stage: str = None,
                              breaker: bool = True) -> None:
        """Count one device failure — the single policy the engine's
        former ad-hoc degrade branches now feed. breaker=False records
        the failure (totals, last_failure) WITHOUT advancing the breaker:
        capacity-local conditions (one oversized sst OOMing its HBM
        prime) are not evidence the device is dead, and must not flap all
        compactions onto cpu."""
        tripped = False
        with self._lock:
            self.device_failure_count += 1
            self.last_failure = {"op": op, "error": str(error)[:400],
                                 "stage": stage, "ts": time.time()}
            if breaker:
                self._consec_failures += 1
                tripped = (self._consec_failures
                           == self.config.breaker_threshold)
                if tripped:
                    self.breaker_trip_count += 1
                    self._breaker_open_until = (
                        time.monotonic() + self.config.breaker_cooldown_s)
        if tripped:
            counters.rate(self.metric_prefix + ".breaker_trip_count").increment()
            counters.number(self.metric_prefix + ".breaker_open").set(1)
            events.emit("lane.breaker_trip", severity="error",
                        lane=self.metric_prefix, op=op,
                        error=str(error)[:200], stage=stage)
            # the trip lands in the active job's timeline too (ISSUE 16):
            # the job that pushed the breaker over names the transition
            from .job_trace import JOB_TRACER

            JOB_TRACER.note("lane.breaker_trip", lane=self.metric_prefix,
                            op=op)

    def record_device_ok(self) -> None:
        with self._lock:
            was_open = self._consec_failures >= self.config.breaker_threshold
            self._consec_failures = 0
            self._breaker_open_until = 0.0
        if was_open:
            counters.number(self.metric_prefix + ".breaker_open").set(0)
            events.emit("lane.breaker_close", lane=self.metric_prefix,
                        via="clean_device_attempt")

    # ----------------------------------------------------------------- run

    def run(self, device_fn, fallback_fn=None, op: str = "compact",
            deadline_s: float = None):
        """Run `device_fn` under the policy; on failure run `fallback_fn`
        (the cpu path, byte-identical by contract). fallback_fn=None means
        the caller wants the device result or the error (bench)."""
        if fallback_fn is not None and self.breaker_open():
            return self._fallback(fallback_fn, op, "breaker open")
        deadline = (self.effective_deadline_s() if deadline_s is None
                    else deadline_s)
        attempts = max(1, self.config.max_retries + 1)
        delay = self.config.backoff_base_s
        last_err = None
        attempt = 0
        compile_until = None  # monotonic end of this call's compile waits
        while attempt < attempts:
            failures_before = self.device_failure_count  #: unguarded_ok racy snapshot: compared against itself below to detect NESTED failures; a concurrent lane's failure only makes the breaker-reset more conservative
            try:
                result = self._attempt(device_fn, deadline, op)
            except KernelCompiling as e:
                if in_guarded_call():
                    raise  # nested guard: the outermost one decides
                if compile_until is None:
                    wait_s = self._compile_wait_s(fallback_fn)
                    compile_until = time.monotonic() + wait_s
                if e.done.wait(max(0.0, compile_until - time.monotonic())):
                    # compiled (or failed to: the next attempt raises the
                    # compiler's error as an ordinary device failure);
                    # not a retry — nothing went wrong on the device
                    continue
                return self._compile_behind(fallback_fn, op, e, wait_s)
            except LaneDeadlineExceeded as e:
                last_err = e
                break  # wedged: never stack retries onto a wedged device
            except Exception as e:  # noqa: BLE001 - every device error is policy input
                last_err = e
                self.record_device_failure(op, repr(e))
                attempt += 1
                if attempt < attempts:
                    with self._lock:
                        self.retry_count += 1
                    counters.rate(self.metric_prefix + ".retry_count").increment()
                    from .job_trace import JOB_TRACER

                    JOB_TRACER.note("lane.retry", lane=self.metric_prefix,
                                    op=op, attempt=attempt,
                                    error=repr(e)[:200])
                    time.sleep(min(delay, self.config.backoff_max_s))
                    delay *= 2
            else:
                # only a CLEAN attempt resets the breaker: a nested
                # guarded call (sharded reassembly sorts re-enter
                # compact_blocks) may have "succeeded" via its own cpu
                # fallback, and crediting that as device health would
                # keep a dead device's breaker from ever accumulating
                if self.device_failure_count == failures_before:  #: unguarded_ok racy snapshot compare (see failures_before above)
                    self.record_device_ok()
                return result
        if fallback_fn is None:
            raise last_err
        return self._fallback(fallback_fn, op,
                              f"device lane failed: {last_err!r}")

    def _compile_wait_s(self, fallback_fn) -> float:
        """How long this call may wait for compiles, all told: what the
        calling thread asked for (`with compile_wait():`), else the whole
        bound when there is no fallback to serve the call, else the
        lane's configured wait (0 in production)."""
        asked = getattr(_CALLER, "compile_wait_s", None)
        if asked is not None:
            return asked
        if fallback_fn is None:
            return COMPILE_BOUND_S
        return self.config.compile_wait_s

    def _compile_behind(self, fallback_fn, op: str, e: KernelCompiling,
                        waited_s: float):
        """The program is not ready and the call will not wait (longer):
        the fallback serves it. Nothing failed, so no fallback/failure
        total moves and the breaker is untouched."""
        with self._lock:
            self.compile_behind_count += 1
            if waited_s > 0:
                self.compile_wait_timeout_count += 1
        counters.rate(self.metric_prefix + ".compile_behind_count").increment()
        from .job_trace import JOB_TRACER

        JOB_TRACER.note("lane.compile_behind", lane=self.metric_prefix,
                        op=op, kernel=e.kernel)
        if fallback_fn is None:
            raise LaneError(f"{op}: {e} after {waited_s:.0f}s")
        return fallback_fn()

    def _attempt(self, fn, deadline_s: float, op: str):
        if not deadline_s or deadline_s <= 0:
            return fn()
        from .job_trace import JOB_TRACER

        box = {}
        done = threading.Event()
        sessions = self.tracer.propagate_sessions()
        with self._lock:
            t = self._idle_workers.pop() if self._idle_workers else None
        if t is None:
            t = _LaneWorker(self)
            t.start()
        t.submit(fn, box, done, sessions, job_id=JOB_TRACER.current())
        if not done.wait(deadline_s):
            # abandoned in its thread; its span stays open so
            # the watchdog keeps attributing the wedge after we move on
            # (the worker rejoins the pool only if the stale call ever
            # finishes — a wedged one never comes back)
            stages = self.tracer.open_stages().get(t.ident)
            stage = stages[-1] if stages else "unknown"
            with self._lock:
                self.deadline_abandon_count += 1
            counters.rate(
                self.metric_prefix + ".deadline_abandon_count").increment()
            err = LaneDeadlineExceeded(
                f"{op}: device call exceeded {deadline_s:.1f}s deadline "
                f"(wedged at stage {stage}); worker abandoned")
            self.record_device_failure(op, str(err), stage=stage)
            raise err
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _fallback(self, fallback_fn, op: str, reason: str):
        with self._lock:
            self.fallback_count += 1
            self.last_fallback = {"op": op, "reason": reason,
                                  "ts": time.time()}
        counters.rate(self.metric_prefix + ".fallback_count").increment()
        events.emit("lane.fallback", severity="warn",
                    lane=self.metric_prefix, op=op, reason=reason[:200])
        from .job_trace import JOB_TRACER

        JOB_TRACER.note("lane.fallback", lane=self.metric_prefix, op=op,
                        reason=reason[:200])
        print(f"[lane-guard:{self.metric_prefix}] {op}: falling back to the "
              f"host path ({reason})", flush=True)
        return fallback_fn()

    # --------------------------------------------------------------- state

    def state(self) -> dict:
        with self._lock:
            open_now = self._consec_failures >= self.config.breaker_threshold
            return {
                "breaker_open": open_now,
                "breaker_consecutive_failures": self._consec_failures,
                "breaker_cooldown_remaining_s": round(
                    max(0.0, self._breaker_open_until - time.monotonic()), 3)
                    if open_now else 0.0,
                "fallbacks": self.fallback_count,
                "retries": self.retry_count,
                "deadline_abandons": self.deadline_abandon_count,
                "breaker_trips": self.breaker_trip_count,
                "device_failures": self.device_failure_count,
                "compile_behind": self.compile_behind_count,
                "compile_wait_timeouts": self.compile_wait_timeout_count,
                "last_failure": self.last_failure,
                "last_fallback": self.last_fallback,
            }

    def reset(self) -> None:
        """Test hook: zero every total and close the breaker."""
        with self._lock:
            self.fallback_count = self.retry_count = 0
            self.deadline_abandon_count = self.breaker_trip_count = 0
            self.device_failure_count = self._consec_failures = 0
            self.compile_behind_count = self.compile_wait_timeout_count = 0
            self._breaker_open_until = 0.0
            self.last_failure = self.last_fallback = None
        counters.number(self.metric_prefix + ".breaker_open").set(0)


def _warm_lane_counters() -> None:
    """Pre-register both lanes' counter sets with literal names (the
    guard instances increment through their metric prefix): /metrics
    shows zeros before the first incident, and tools/check_metric_names
    can tie each README row to a registration."""
    counters.rate("compact.lane.fallback_count")
    counters.rate("compact.lane.retry_count")
    counters.rate("compact.lane.deadline_abandon_count")
    counters.rate("compact.lane.breaker_trip_count")
    counters.number("compact.lane.breaker_open")
    counters.rate("compact.lane.compile_behind_count")
    counters.rate("read.lane.fallback_count")
    counters.rate("read.lane.retry_count")
    counters.rate("read.lane.deadline_abandon_count")
    counters.rate("read.lane.breaker_trip_count")
    counters.number("read.lane.breaker_open")
    counters.rate("read.lane.compile_behind_count")


_warm_lane_counters()

# process-wide instance: every device-backed merge in this process shares
# one breaker (one device per process is the deployment shape)
LANE_GUARD = LaneGuard(LaneGuardConfig.from_env())

# the serving read lane (device point lookups, ops/device_lookup.py via
# engine/db.py get_batch): its OWN breaker/totals so a wedged read probe
# degrades reads to the host walk without routing compactions off the
# device (and a compaction wedge doesn't blind the read path). The default
# 30 s deadline undercuts the compact lane's 120 s floor: reads are
# latency-sensitive and the byte-identical host walk is always ready.
READ_LANE_GUARD = LaneGuard(
    LaneGuardConfig.from_env("PEGASUS_READ_LANE", deadline_s=30.0),
    metric_prefix="read.lane")
