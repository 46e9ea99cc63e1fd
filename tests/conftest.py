"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

Must run before any jax import anywhere in the test session, hence env vars
set at conftest import time. Mirrors the reference's approach of testing
multi-node behavior on one machine (onebox, run.sh:480).
"""

import os

# arm the lock-order deadlock detector for the WHOLE suite (ISSUE 9):
# every named lock records its acquisition graph, a cycle = a deadlock
# waiting for the right interleaving, and pytest_sessionfinish below
# fails the run on any recorded violation — so every onebox /
# group-worker / chaos test doubles as a lock-order regression test.
# Must happen before any pegasus_tpu import (locks are created at class
# init with the env read per factory call); subprocesses (group workers,
# killed-node oneboxes) inherit both knobs and report
# violations into the shared file.
os.environ.setdefault("PEGASUS_LOCKRANK", "1")
_LOCKRANK_FILE_PRESET = "PEGASUS_LOCKRANK_FILE" in os.environ
_LOCKRANK_FILE = os.environ.setdefault(
    "PEGASUS_LOCKRANK_FILE", f"/tmp/pegasus_lockrank_{os.getpid()}.jsonl")
if not _LOCKRANK_FILE_PRESET:
    # OUR file (pid-named): drop any leftover from a crashed prior run
    # with a recycled pid so stale violations can't fail a green session
    try:
        os.unlink(_LOCKRANK_FILE)
    except OSError:
        pass
# an externally-owned file is never deleted and only NEW lines count:
# remember how many were already there when the session began
try:
    with open(_LOCKRANK_FILE) as _f:
        _LOCKRANK_BASELINE_LINES = sum(1 for line in _f if line.strip())
except OSError:
    _LOCKRANK_BASELINE_LINES = 0

# tests always run on the virtual CPU mesh unless explicitly opted onto
# hardware; the platform is named EXPLICITLY, which is also what lets
# backend="tpu" engines open here (base/utils.py open_device_backend)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
if not os.environ.get("PEGASUS_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

# rebuild any stale native artifact BEFORE the first pegasus_tpu import
# caches a loaded .so (ISSUE 20): tier-1 must never silently exercise a
# binary older than its C source. Failures degrade loudly to the
# pure-Python twins and never fail collection.
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
try:
    from tools import build_native  # noqa: E402

    build_native.ensure()
except Exception as _e:  # noqa: BLE001 - the gate is best-effort
    print(f"[conftest] build_native: {_e!r}")

# persistent compile cache: the suite jit-compiles many static shapes; cold
# runs took 7 minutes in round 1
from pegasus_tpu.base.utils import enable_compile_cache  # noqa: E402

enable_compile_cache()

# the suite is about the DEVICE kernels: a guarded call waits for a kernel
# that is still compiling, so every first call of a shape still runs on the
# jax platform. Production never waits on the write or read path — the host
# lane serves such a call (runtime/lane_guard.py, COMPILE-BEHIND);
# tests/test_lane_guard.py pins that policy explicitly
from pegasus_tpu.runtime.lane_guard import (LANE_GUARD,  # noqa: E402
                                            READ_LANE_GUARD)

LANE_GUARD.config.compile_wait_s = 600.0
READ_LANE_GUARD.config.compile_wait_s = 600.0


def _reap_group_workers():
    """Kill any partition-group executor the suite (or a crashed test)
    left behind: workers are separate OS processes (`-m pegasus_tpu.server
    --group-worker`), and a leaked one would hold its engine dirs and
    sockets past the run. Normal teardown (GroupedReplicaNode.stop or
    control-channel EOF) exits them; this is the backstop that keeps
    tier-1 leak-free no matter how a test died."""
    import signal

    me = os.getpid()
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\x00", b" ").decode(errors="replace")
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        # scope the kill: only THIS session's children and true orphans
        # (ppid 1 = a worker whose parent already died) — never another
        # concurrent run's live workers
        if "--group-worker" in cmd and ppid in (me, 1):
            print(f"[conftest] reaping leaked group worker pid={pid}")
            try:
                os.kill(int(pid), signal.SIGKILL)
            except OSError:
                pass


def pytest_sessionfinish(session, exitstatus):
    """Join the process-wide daemon executors BEFORE interpreter exit.

    The long-standing "rc=134/139 after 'N passed'" shutdown crash
    (CHANGES PR 3/4): CPython finalization kills daemon threads at an
    arbitrary bytecode boundary, and the suite leaves three kinds of them
    alive — the compact pipeline/install pool workers and the
    device-watchdog probe loop — all of which may be INSIDE an XLA
    dispatch (watchdog probes jit a kernel on a cadence; pool workers run
    deferred installs/primes). A worker killed mid-dispatch dies holding
    TSL/XLA resources, and the C++ static teardown then aborts
    ("terminate called without an active exception") AFTER pytest printed
    its summary — so the tier-1 command's rc lied about a green run.
    Stopping the watchdog and joining the pools (bounded: ThreadPool.stop
    joins with a 5 s timeout per worker) drains the process of
    XLA-touching daemons before Py_Finalize runs."""
    try:
        from pegasus_tpu.ops import pipeline
        from pegasus_tpu.ops.device_watchdog import WATCHDOG

        WATCHDOG.stop()
        t = getattr(WATCHDOG, "_loop_thread", None)
        if t is not None and t.is_alive():
            t.join(timeout=5)
        with pipeline._POOL_LOCK:
            pools = [p for p in (pipeline._POOL, pipeline._IO_POOL,
                                 pipeline._COMPILE_POOL)
                     if p is not None]
        for p in pools:
            p.stop()
        # the tracked-spawn registry is the GENERAL backstop for the
        # same bug class: shut down every tracked executor and join
        # every tracked daemon (bounded) so no thread the registry knows
        # about can die inside an XLA dispatch during Py_Finalize
        from pegasus_tpu.runtime.tasking import TRACKED

        leftover = TRACKED.join_all(timeout_s=5.0)
        if leftover:
            print(f"[conftest] {len(leftover)} tracked thread(s) still "
                  f"alive at teardown: "
                  f"{sorted(t.name for t in leftover)[:10]}")
    except Exception as e:  # teardown must never mask the run's outcome
        print(f"[conftest] executor teardown: {e!r}")
    try:
        _reap_group_workers()
    except Exception as e:  # the reaper is best-effort
        print(f"[conftest] group-worker reap: {e!r}")
    try:
        _check_lockrank(session)
    except Exception as e:  # the gate must never mask the run's outcome
        print(f"[conftest] lockrank gate: {e!r}")


def _check_lockrank(session):
    """Fail the session on any lock-order cycle recorded this run — in
    THIS process (GRAPH.violations) or by any subprocess (group workers,
    chaos-killed oneboxes) that appended to the shared violation file."""
    from pegasus_tpu.runtime import lockrank

    import json

    violations = list(lockrank.GRAPH.violations)
    try:
        with open(_LOCKRANK_FILE) as f:
            file_lines = [line.strip() for line in f if line.strip()]
    except OSError:
        file_lines = []
    # only lines THIS session appended count (an externally-owned file
    # may carry history)...
    file_lines = file_lines[_LOCKRANK_BASELINE_LINES:]

    # ...and in-process violations land in BOTH the graph and the file;
    # count the file only for other pids (subprocess reports)
    def _other_pid(line):
        try:
            return json.loads(line).get("pid") != os.getpid()
        except ValueError:
            return True
    file_lines = [line for line in file_lines if _other_pid(line)]
    if not _LOCKRANK_FILE_PRESET:
        # our pid-named file; an externally-owned one stays for its owner
        try:
            os.unlink(_LOCKRANK_FILE)
        except OSError:
            pass
    n = len(violations) + len(file_lines)
    if not n:
        return
    print(f"\n[conftest] LOCKRANK: {n} lock-order violation(s) recorded "
          f"this session — each is a deadlock waiting for the right "
          f"interleaving:")
    for v in violations:
        print(f"  in-process: {' -> '.join(v['cycle'])} "
              f"({v['held_site']} vs {v['acquire_site']})")
    for line in file_lines:
        print(f"  subprocess: {line}")
    if session.exitstatus == 0:
        session.exitstatus = 1
