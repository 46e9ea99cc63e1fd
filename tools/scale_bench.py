"""North-star-scale benchmark: BASELINE.json's 100M-key fillrandom+compact
config (reference pegasus_bench fillrandom + manual compact over a 100M-key
table), exercising the bigger-than-device blockwise path at the scale it
was built for.

Unlike bench.py (which times the raw backend lanes), both lanes here go
through ops.compact.compact_blocks — so with PEGASUS_SCALE_MAXDEV below the
input size the device lane takes `_compact_blockwise` (ops/compact.py:651):
disjoint key ranges compacted independently, outputs concatenated, the
byte-equality contract checked against the native CPU lane's digest.

Bounded like every tool in tools/: a watchdog thread fails the run
(reason + progress on stderr, non-zero exit, no result line) after
PEGASUS_SCALE_TIMEOUT_S (default 5400 s — the 100M fill alone takes
minutes), and the device lane also honors PEGASUS_SCALE_FAKE=sleep (test
hook simulating a wedged device mid-lane). The device lane refuses a
non-TPU platform unless JAX_PLATFORMS names one explicitly.

Env: PEGASUS_SCALE_N (default 100_000_000), PEGASUS_SCALE_MAXDEV (default
16M records — forces ~13 range blocks at 100M), PEGASUS_SCALE_RUNS (4),
PEGASUS_SCALE_VALUE (100), PEGASUS_SCALE_TIMEOUT_S; JAX_PLATFORMS=cpu
rehearses the device lane on XLA:CPU (the metric's name then says so).
"""

import hashlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_PRINTED = False


def _emit(result: dict) -> None:
    global _PRINTED
    _PRINTED = True  # before printing: the watchdog thread checks it
    print(json.dumps(result), flush=True)


def _params():
    return (int(os.environ.get("PEGASUS_SCALE_N", 100_000_000)),
            int(os.environ.get("PEGASUS_SCALE_RUNS", 4)),
            int(os.environ.get("PEGASUS_SCALE_VALUE", 100)),
            int(os.environ.get("PEGASUS_SCALE_MAXDEV", 16 << 20)))


def _metric(n, n_runs, value_size, maxdev, platform: str) -> str:
    return (f"blockwise fillrandom+compact at north-star scale, device "
            f"lane on jax platform {platform} "
            f"({n} records, {n_runs} runs, value={value_size}B, "
            f"max_device_records={maxdev})")


def _fail_message(reason: str) -> None:
    """stdout carries results only: a run with no device number says why
    on stderr (with whatever the host lanes measured, as diagnostics)."""
    print(f"scale_bench FAILED: {reason}; progress: {json.dumps(_PROGRESS)}",
          file=sys.stderr, flush=True)


_PROGRESS = {}


def _arm_watchdog():
    import threading

    budget = int(os.environ.get("PEGASUS_SCALE_TIMEOUT_S", 5400))
    if budget <= 0:
        return

    def boom():
        if _PRINTED:
            os._exit(0)  # the result is out; only teardown stalled
        _fail_message(f"watchdog fired after {budget}s")
        os._exit(1)

    t = threading.Timer(budget, boom)
    t.daemon = True
    t.start()


def _digest(block) -> dict:
    return {"n_out": int(block.n),
            "key_sha": hashlib.sha256(block.key_arena).hexdigest(),
            "val_sha": hashlib.sha256(block.val_arena).hexdigest()}


def main():
    _arm_watchdog()
    n, n_runs, value_size, maxdev = _params()

    import bench  # reuse the deterministic vectorized fill

    from pegasus_tpu.ops.compact import CompactOptions, compact_blocks

    t0 = time.perf_counter()
    runs, fill_s = bench._fill(n, n_runs, value_size)
    _PROGRESS["fill_s"] = round(fill_s, 3)
    print(f"scale: filled {n} records in {fill_s:.1f}s",
          file=sys.stderr, flush=True)

    cpu_opts = CompactOptions(backend="cpu", now=100, bottommost=True,
                              runs_sorted=True)
    t1 = time.perf_counter()
    cpu = compact_blocks(runs, cpu_opts)
    cpu_s = time.perf_counter() - t1
    cpu_dig = _digest(cpu.block)
    del cpu
    _PROGRESS.update(cpu_compact_s=round(cpu_s, 3),
                     output_records=cpu_dig["n_out"])
    print(f"scale: cpu lane {cpu_s:.1f}s "
          f"({int(n / cpu_s)} rec/s, {cpu_dig['n_out']} survivors)",
          file=sys.stderr, flush=True)

    if os.environ.get("PEGASUS_SCALE_FAKE") == "sleep":
        time.sleep(3600)  # test hook: device lane wedges

    from pegasus_tpu.base.utils import open_device_backend

    device = open_device_backend()
    dev_opts = CompactOptions(backend="tpu", now=100, bottommost=True,
                              runs_sorted=True, max_device_records=maxdev)
    assert n > maxdev, "device lane would not take the blockwise path"
    from pegasus_tpu.runtime.lane_guard import LANE_GUARD, compile_wait

    t2 = time.perf_counter()
    with compile_wait():  # the device lane or nothing: wait for cold kernels
        dev = compact_blocks(runs, dev_opts)
    dev_s = time.perf_counter() - t2
    dev_dig = _digest(dev.block)
    del dev
    lane = LANE_GUARD.state()
    if lane["fallbacks"] or lane["compile_behind"]:
        # the cpu lane served (part of) it: not a device number
        _fail_message(f"device lane fell to the host: {json.dumps(lane)}")
        sys.exit(4)

    byte_equal = dev_dig == cpu_dig
    speedup = cpu_s / dev_s
    _emit({
        "metric": _metric(n, n_runs, value_size, maxdev, device["platform"]),
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "detail": {
            "fill_s": round(fill_s, 3),
            "cpu_compact_s": round(cpu_s, 3),
            "device_compact_s": round(dev_s, 3),
            "input_records": n,
            "output_records": cpu_dig["n_out"],
            "byte_equal": byte_equal,
            "device": device,
            "blocks": -(-n // maxdev),
            "total_s": round(time.perf_counter() - t0, 1),
        },
    })
    if not byte_equal:
        sys.exit(3)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 - boundary: name the failure, exit non-zero
        import traceback

        traceback.print_exc()
        _fail_message(f"{type(e).__name__}: {str(e)[:300]}")
        sys.exit(1)
