"""DeviceKernel: every jitted program of the package, compiled explicitly
and never under a lane deadline.

A cold XLA:TPU compile of a merge network takes one to three minutes on a
v5e (PERF.md section 5) and the read kernels one to four seconds per
(run shape, query bucket) — while the compaction lane's deadline floor is
120 s, the read lane's 30 s, and most guarded calls sit inside a write or
read RPC with a 10 s client timeout. So compilation is taken off that
path instead of being waited for on it:

  - called on a lane worker (under a guard's deadline), a kernel whose
    program is not compiled yet hands the compile to the compile pool and
    raises KernelCompiling; the guard serves the call from its fallback
    and counts it, or — for a caller that asked for the device — waits on
    the caller's thread outside the deadline (runtime/lane_guard.py,
    COMPILE-BEHIND);
  - called anywhere else (a residency prime, a bench, a test calling a
    backend directly) it compiles on the calling thread, or waits for the
    thread already compiling that program, for at most COMPILE_BOUND_S.

Either way a program compiles ONCE however many threads want it, under a
`compile` span (stage attribution: the watchdog and compact-trace-dump
see it), and the totals ride in device-health's `compile` block.

Programs are keyed on the full input signature (tree structure, shapes,
dtypes, committed shardings) — what jax.jit itself would retrace on — so
a builder cached on the static shapes may still be called with a varying
batch axis or mesh placement (ops/batched_compact.py).
"""

import atexit
import threading
import time

from ..runtime import lockrank
from ..runtime.lane_guard import (COMPILE_BOUND_S, KernelCompiling,
                                  in_guarded_call)
from ..runtime.tracing import COMPACT_TRACER as _TRACE
from .pipeline import compile_pool

_LOCK = lockrank.named_lock("kernel.compile")
# monotonic totals + the in-flight gauge (device-health `compile`)
_STATS = {"compiled": 0, "failed": 0, "inflight": 0,
          "seconds": 0.0, "max_s": 0.0, "kernels": {}}  #: guarded_by _LOCK


def compile_report() -> dict:
    """-> {compiled, failed, inflight, seconds, max_s, kernels}: how many
    programs this process compiled (or loaded from the persistent cache),
    how many the compiler refused, how many are compiling right now, what
    the finished ones cost, and the compiled count per kernel name (which
    kernels this process has actually built for its platform)."""
    with _LOCK:
        return dict(_STATS, seconds=round(_STATS["seconds"], 3),
                    max_s=round(_STATS["max_s"], 3),
                    kernels=dict(_STATS["kernels"]))


@atexit.register
def _drain_at_exit() -> None:
    """Interpreter exit with a compile in flight aborts the process from
    C++ teardown (the pool worker is inside XLA, not holding the GIL):
    let the compiles end first. Bounded like every other compile wait."""
    deadline = time.monotonic() + COMPILE_BOUND_S
    while compile_report()["inflight"] and time.monotonic() < deadline:
        time.sleep(0.1)


class _Program:
    """One compiled executable (or the compiler's error) for one input
    signature; `done` is set when the compile ends, either way."""

    __slots__ = ("exe", "error", "done")

    def __init__(self):
        self.exe = None
        self.error = None
        self.done = threading.Event()


def _signature(args):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(
        (a.shape, a.dtype,
         a.sharding if getattr(a, "committed", False) else None)
        for a in leaves)


class DeviceKernel:
    """jax.jit(fn) with the compile made explicit (module docstring).
    Arguments are arrays (numpy or jax), as every kernel here is called."""

    def __init__(self, fn, name: str):
        import jax

        def kernel(*args):
            return fn(*args)

        # the jitted function's name is the program's name: in profiler
        # traces, and as the `jit_pegasus_<name>-<hash>` file the
        # persistent compile cache keeps it under (which tells the
        # package's kernels from jax's own small eager programs)
        kernel.__name__ = kernel.__qualname__ = f"pegasus_{name}"
        self._jit = jax.jit(kernel)
        self.name = name
        self._programs = {}  #: guarded_by _LOCK

    def __call__(self, *args):
        sig = _signature(args)
        prog = self._programs.get(sig)  #: unguarded_ok GIL-atomic dict read; a miss re-checks under the lock
        if prog is None:
            prog = self._start(sig, args)
        if not prog.done.is_set():
            if in_guarded_call():
                raise KernelCompiling(self.name, prog.done)
            if not prog.done.wait(COMPILE_BOUND_S):
                raise TimeoutError(f"kernel {self.name} still compiling "
                                   f"after {COMPILE_BOUND_S:.0f}s")
        if prog.error is not None:
            raise prog.error
        return prog.exe(*args)

    def _start(self, sig, args) -> _Program:
        with _LOCK:
            prog = self._programs.get(sig)
            if prog is not None:
                return prog
            prog = self._programs[sig] = _Program()
            _STATS["inflight"] += 1
        if in_guarded_call():
            # the args stay referenced until the compile ends (what a
            # lowering needs: shapes, dtypes, placements) — a few MB of
            # HBM for at most one compile's duration
            try:
                compile_pool().enqueue(self._compile, prog, args)
            except RuntimeError as e:  # pool stopped: the process is exiting
                self._finish(prog, e, 0.0)
        else:
            self._compile(prog, args)
        return prog

    def _compile(self, prog: _Program, args) -> None:
        t0 = time.monotonic()
        error = None
        try:
            with _TRACE.span("compile"):
                prog.exe = self._jit.lower(*args).compile()
        except Exception as e:  # noqa: BLE001 - handed to every caller of this program
            error = e
            print(f"[kernel] {self.name}: compile failed: {e!r}", flush=True)
        finally:
            self._finish(prog, error, time.monotonic() - t0)

    def _finish(self, prog: _Program, error, took: float) -> None:
        prog.error = error
        with _LOCK:
            _STATS["inflight"] -= 1
            if error is not None:
                _STATS["failed"] += 1
            else:
                _STATS["compiled"] += 1
                _STATS["kernels"][self.name] = _STATS["kernels"].get(
                    self.name, 0) + 1
            _STATS["seconds"] += took
            _STATS["max_s"] = max(_STATS["max_s"], took)
        prog.done.set()
