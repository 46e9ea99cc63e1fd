"""The one chip-holding process of a served cell: `pegasus_tpu.server` run
as `__main__`, exactly as `python -m pegasus_tpu.server --config <ini>`
runs it, plus one side thread that starts and stops jax's profiler when a
marker file appears in the control directory and, once asked, reduces the
trace to numbers there. The same launcher serves traced and untraced runs,
so the only difference between them is the profiler. (Only the process
that holds the chip can trace it; a profiler switch in the server itself
would let this file go.)

    python3 benchmarks/lib/serverproc.py --config <ini> --control <dir>

Markers, made by the parent: `trace.start`, `trace.stop`, `trace.reduce`
(holds the traced window's seconds). Answers, made here: `trace.started`,
`trace.stopped`, `trace.json`.
"""

import argparse
import json
import os
import runpy
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def profiler_switch(control: str) -> None:
    from benchmarks.lib import device, markers, tracered

    p = lambda name: os.path.join(control, name)  # noqa: E731
    markers.wait(p("trace.start"))
    with device.profiled(p("trace"), True):
        markers.put(p("trace.started"), repr(time.time()))
        markers.wait(p("trace.stop"))
    markers.put(p("trace.stopped"), repr(time.time()))
    window_s = float(markers.wait(p("trace.reduce")))
    try:
        t0 = time.monotonic()
        planes = tracered.load(p("trace"))
        t1 = time.monotonic()
        out = tracered.reduce(planes, window_s)
        out["load_s"], out["reduce_s"] = t1 - t0, time.monotonic() - t1
        print(f"[bench] trace of {window_s:.1f}s: tracered.load "
              f"{out['load_s']:.1f}s, tracered.reduce {out['reduce_s']:.1f}s",
              flush=True)
        tracered.debug_dump(planes)
    except Exception as e:  # noqa: BLE001 - told to the parent, which fails the run
        out = {"error": repr(e)}
    markers.put(p("trace.json"), json.dumps(out))


def plant_fault(kind: str) -> None:
    """For tests/test_faults.py only (BENCH_FAULT in the environment, which
    no benchmark run sets): break the served path underneath the harness,
    so the test can see `correct` come out false.
      drop_update   every 7th decree is acknowledged and applied as empty
                    on every replica: the state stays unchanged
      alter_answer  every 13th point read that finds a value answers it
                    with one byte flipped"""
    from pegasus_tpu.engine.db import LsmEngine, WriteBatch

    if kind == "drop_update":
        write, write_batch = LsmEngine.write, LsmEngine.write_batch

        def hollow(batch, decree):
            return WriteBatch() if decree % 7 == 3 else batch

        LsmEngine.write = lambda self, batch, decree: write(
            self, hollow(batch, decree), decree)
        LsmEngine.write_batch = lambda self, pairs: write_batch(
            self, [(hollow(b, d), d) for b, d in pairs])
    elif kind == "alter_answer":
        get, get_batch, seen = LsmEngine.get, LsmEngine.get_batch, [0]

        def alter(value):
            if value is None:
                return None
            seen[0] += 1
            if seen[0] % 13:
                return value
            value = bytes(value)
            return value[:-1] + bytes([value[-1] ^ 1])

        LsmEngine.get = lambda self, key, now=None: alter(
            get(self, key, now=now))
        LsmEngine.get_batch = lambda self, keys, now=None: [
            alter(v) for v in get_batch(self, keys, now=now)]
    else:
        raise SystemExit(f"unknown BENCH_FAULT {kind!r}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--control", required=True)
    ns = ap.parse_args()
    sys.path.insert(0, ROOT)
    threading.Thread(target=profiler_switch, args=(ns.control,),
                     daemon=True, name="bench-profiler-switch").start()
    if os.environ.get("BENCH_FAULT"):
        plant_fault(os.environ["BENCH_FAULT"])
    sys.argv = ["pegasus-server", "--config", ns.config]
    runpy.run_module("pegasus_tpu.server", run_name="__main__",
                     alter_sys=True)


if __name__ == "__main__":
    main()
