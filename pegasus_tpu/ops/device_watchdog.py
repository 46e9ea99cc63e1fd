"""Device-health watchdog: is the TPU backend alive — and if not, WHERE
did it wedge?

A bare timeout ("tpu lane exceeded 360s") cannot distinguish a device
wedged in backend init from one wedged mid-kernel or mid-transfer. The
watchdog probes backend liveness with a tiny jit round-trip executed in a
SUBORDINATE daemon thread under a timeout, so the probe can hang without
hanging the caller — and a hung probe thread is simply abandoned (python
cannot kill a thread blocked inside the backend).

State it records:

  last_ok          wall time of the last successful probe
  wedged_at_stage  the innermost open tracing span (runtime/tracing.py)
                   once fail_threshold CONSECUTIVE probes failed (one
                   starved probe behind a long-but-healthy kernel is an
                   error, not a wedge) — "device_init", "pack", "h2d",
                   "device", "gather", or "idle" when nothing was in
                   flight.

Counters (one registry with everything else — /metrics serves them):
  compact.watchdog.probe_count / probe_failures   rate
  compact.watchdog.probe_us                       percentile
  compact.watchdog.wedged                         gauge (0/1)

start() arms a background loop that re-probes every interval_s.
probe_fn is injectable for tests (a deliberately-hung fake backend
exercises the timeout path without hardware).
"""

import threading
import time

from ..runtime.perf_counters import counters
from ..runtime.tracing import COMPACT_TRACER

_PROBE_JIT = []  # compiled once; a fresh jit per probe would re-trace

# monotonic totals of the production policies that quietly keep work off
# the device (registered where each policy lives)
BYPASS_COUNTERS = ("engine.hbm.long_key_bypass_count",
                   "engine.hbm.prime_fail_count",
                   "read.device.fence_fail_count",
                   "engine.compact.mesh_fail_count")


def _default_probe() -> bool:
    """Tiny jit round-trip; blocks iff the backend is wedged."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if not _PROBE_JIT:
        _PROBE_JIT.append(jax.jit(lambda x: x + jnp.int32(1)))
    out = np.asarray(_PROBE_JIT[0](jnp.zeros(8, jnp.int32)))
    return int(out[0]) == 1


class DeviceHealthWatchdog:
    def __init__(self, probe_timeout_s: float = 10.0,
                 interval_s: float = 5.0, probe_fn=None,
                 tracer=COMPACT_TRACER, fail_threshold: int = 2):
        self.probe_timeout_s = probe_timeout_s
        self.interval_s = interval_s
        self.probe_fn = probe_fn or _default_probe
        self.tracer = tracer
        # one slow-but-healthy kernel can legitimately starve a probe past
        # its timeout (device work serializes); only consecutive failures
        # flip the wedged state, so a single starved probe records an
        # error without a false wedge verdict
        self.fail_threshold = fail_threshold
        self._lock = threading.Lock()
        self._probe_thread = None  # in-flight (possibly hung) probe
        self._consec_failures = 0
        self.last_ok = None
        self.last_error = None
        self.wedged_at_stage = None
        self._stop = threading.Event()
        self._loop_thread = None

    # ------------------------------------------------------------- probing

    def probe(self, timeout_s: float = None) -> bool:
        """One liveness round-trip under a timeout. False = wedged (or the
        previous probe never came back — no stacking of hung threads)."""
        timeout = self.probe_timeout_s if timeout_s is None else timeout_s
        with self._lock:
            if self._probe_thread is not None and self._probe_thread.is_alive():
                pass  # previous probe still hung: fail fast below
            else:
                self._probe_thread = None
            hung = self._probe_thread is not None
        counters.rate("compact.watchdog.probe_count").increment()
        if hung:
            self._mark_failed("previous probe still hung")
            return False
        result = {}

        def run():
            try:
                result["ok"] = bool(self.probe_fn())
            except Exception as e:  # noqa: BLE001 - a probe error IS the signal
                result["error"] = repr(e)

        from ..runtime.tasking import spawn_thread

        # never joined on timeout by design: a wedged probe is abandoned
        # (the registry still tracks it)
        t = spawn_thread(run, daemon=True, name="device-probe", start=False)
        with self._lock:
            self._probe_thread = t
        t0 = time.perf_counter()
        t.start()
        t.join(timeout)
        counters.percentile("compact.watchdog.probe_us").set(
            int((time.perf_counter() - t0) * 1e6))
        if t.is_alive():
            # the probe is wedged inside the backend; leave the daemon
            # thread behind
            self._mark_failed(f"probe timed out after {timeout}s")
            return False
        with self._lock:
            self._probe_thread = None
        if result.get("ok"):
            with self._lock:
                self.last_ok = time.time()
                self.last_error = None
                self.wedged_at_stage = None
                self._consec_failures = 0
            counters.number("compact.watchdog.wedged").set(0)
            return True
        self._mark_failed(result.get("error", "probe returned falsy"))
        return False

    def _mark_failed(self, error: str):
        inner = self.tracer.innermost_open()
        with self._lock:
            self.last_error = error
            self._consec_failures += 1
            wedged = self._consec_failures >= self.fail_threshold
            if wedged:
                self.wedged_at_stage = inner[0] if inner else "idle"
        counters.rate("compact.watchdog.probe_failures").increment()
        if wedged:
            counters.number("compact.watchdog.wedged").set(1)

    # -------------------------------------------------------------- state

    def state(self) -> dict:
        with self._lock:
            out = {"last_ok": self.last_ok,
                   "last_error": self.last_error,
                   "wedged_at_stage": self.wedged_at_stage}
        out["open_stages"] = {str(tid): stages for tid, stages
                              in self.tracer.open_stages().items()}
        # what every health surface this state feeds (the device-health
        # remote command, /compact/trace) must
        # be able to answer without reaching into the process: WHICH
        # device the kernels run on, where the persistent compile cache
        # is, what compilation cost and whether any is in flight, both
        # lane guards' totals, and the quiet bypasses that leave a file
        # host-served
        from ..base.utils import device_report
        from ..runtime.lane_guard import LANE_GUARD, READ_LANE_GUARD
        from .kernel import compile_report

        out.update(device_report())
        out["compile"] = compile_report()
        out["lane"] = LANE_GUARD.state()
        out["read_lane"] = READ_LANE_GUARD.state()
        out["bypass"] = {name: counters.number(name).value()
                         for name in BYPASS_COUNTERS}
        return out

    # ----------------------------------------------------------- lifecycle

    def start(self):
        """Arm the background probe loop (idempotent)."""
        with self._lock:
            if self._loop_thread is not None and self._loop_thread.is_alive():
                return self
            from ..runtime.tasking import spawn_thread

            self._stop.clear()
            self._loop_thread = spawn_thread(
                self._loop, daemon=True, name="device-watchdog",
                start=False)
        self._loop_thread.start()
        return self

    def stop(self):
        self._stop.set()

    def _loop(self):
        # first probe immediately: a wedge during device init should be
        # attributable even if it happens before the first interval elapses
        while True:
            try:
                self.probe()
            except Exception as e:  # noqa: BLE001 - loop must survive
                print(f"[device-watchdog] probe crashed: {e!r}", flush=True)
            if self._stop.wait(self.interval_s):
                return


# process-wide instance: the manual-compact service arms its loop around
# tpu compactions, the lane guards' breakers re-probe the device through it
WATCHDOG = DeviceHealthWatchdog()
