"""Engine-level compaction benchmark: LsmEngine.manual_compact, cpu vs tpu.

The SYSTEM number, distinct from bench.py's kernel number: wall-clock of
a full manual compaction through the real engine — SST loads, the
device-resident run cache (backend=tpu packs+uploads each file once, then
merges read HBM), merge/dedup/filter, output-file split, manifest swap. Mirrors the reference's pegasus_manual_compact timing over
a filled table (scripts/pegasus_manual_compact.sh flow).

Usage:
    python tools/engine_bench.py            # all lanes, default sizes
    PEGASUS_EBENCH_N=2000000 PEGASUS_EBENCH_BACKENDS=tpu python tools/...

Lanes (PEGASUS_EBENCH_BACKENDS, default "cpu,tpu,tpu_dv"): cpu, tpu
(host-gather materialization), tpu_dv (EngineOptions.device_values —
output values materialize on device; the measurement that decides
whether the flag defaults on). Prints one JSON line per lane + a final
comparison line of cpu vs the best tpu lane.

Bounded: a watchdog fails the run (reason + completed lanes on stderr,
non-zero exit, no comparison line) after PEGASUS_EBENCH_TIMEOUT_S (default
1200 s) — a wedged backend init can stall the tpu lanes forever, and no
tool may be able to hang its caller. PEGASUS_EBENCH_FAKE=sleep simulates
that wedge (tests). The tpu lanes refuse a non-TPU platform unless
JAX_PLATFORMS names one explicitly.
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

_RESULTS = {}  # lanes completed so far (the watchdog reports them)
_PRINTED_FINAL = False


def _arm_watchdog():
    import threading

    budget = int(os.environ.get("PEGASUS_EBENCH_TIMEOUT_S", 1200))
    if budget <= 0:
        return

    def boom():
        if _PRINTED_FINAL:
            os._exit(0)  # the result is out; only teardown stalled
        print(f"engine_bench FAILED: watchdog fired after {budget}s; "
              f"completed lanes: "
              f"{ {k: v.get('manual_compact_s') for k, v in _RESULTS.items()} }",
              file=sys.stderr, flush=True)
        os._exit(1)

    t = threading.Timer(budget, boom)
    t.daemon = True
    t.start()


def build_table(path: str, backend: str, n: int, value_size: int,
                n_files: int, device_values: bool = False):
    """Fill a table: n records across n_files L0 SSTs with overlapping
    hashkeys (dedup work exists), no auto-compaction."""
    from bench import make_run, presort_run
    from pegasus_tpu.engine import EngineOptions, LsmEngine
    from pegasus_tpu.engine.sstable import SSTable, write_sst

    opts = EngineOptions(backend=backend, l0_compaction_trigger=1 << 30,
                         level_base_bytes=1 << 62,
                         device_values=device_values)
    eng = LsmEngine(path, opts)
    per = n // n_files
    for s in range(n_files):
        blk = presort_run(make_run(per, value_size, seed=s,
                                   key_space=max(1, n // 2)))
        with eng._lock:
            name = eng._alloc_file_locked()
        write_sst(os.path.join(path, name), blk,
                  {"level": 0, "last_flushed_decree": s + 1})
        sst = SSTable(os.path.join(path, name))
        sst._block = blk
        if backend == "tpu":
            # flush-time residency prime (values too when the lane says so)
            sst.device_run(opts.prefix_u32, with_values=device_values)
        with eng._lock:
            eng._l0.insert(0, sst)
            eng._write_manifest_locked()
    return eng


def run_lane(lane: str, root: str, n: int, value_size: int,
             n_files: int, reps: int) -> dict:
    backend = "tpu" if lane.startswith("tpu") else "cpu"
    device_values = lane == "tpu_dv"
    path = os.path.join(root, lane)
    shutil.rmtree(path, ignore_errors=True)
    t0 = time.perf_counter()
    eng = build_table(path, backend, n, value_size, n_files, device_values)
    fill_s = time.perf_counter() - t0
    best = float("inf")
    stats = {}
    for rep in range(reps):
        if rep > 0:
            # rebuild the L0 state so every rep compacts the same input
            eng.close()
            shutil.rmtree(path, ignore_errors=True)
            eng = build_table(path, backend, n, value_size, n_files,
                              device_values)
        t0 = time.perf_counter()
        stats = eng.manual_compact(now=100)
        best = min(best, time.perf_counter() - t0)
    digest = table_digest(eng)
    eng.close()
    return {"backend": lane, "fill_s": round(fill_s, 3),
            "manual_compact_s": round(best, 3),
            "records_per_s": int(stats.get("input_records", n) / best),
            "stats": stats, "digest": digest}


def table_digest(eng) -> str:
    """Order-sensitive digest over every output record (byte-equality
    check between lanes)."""
    import hashlib

    h = hashlib.sha256()
    with eng._lock:
        files = list(eng._l0) + [f for lv in sorted(eng._levels)
                                 for f in eng._levels[lv]]
    for sst in files:
        b = sst.block()
        h.update(b.key_arena.tobytes())
        h.update(b.val_arena.tobytes())
    return h.hexdigest()[:16]


def main():
    global _PRINTED_FINAL
    _arm_watchdog()
    n = int(os.environ.get("PEGASUS_EBENCH_N", 2_000_000))
    value_size = int(os.environ.get("PEGASUS_EBENCH_VALUE", 100))
    n_files = int(os.environ.get("PEGASUS_EBENCH_FILES", 4))
    reps = int(os.environ.get("PEGASUS_EBENCH_REPS", 2))
    backends = os.environ.get("PEGASUS_EBENCH_BACKENDS",
                              "cpu,tpu,tpu_dv").split(",")
    root = os.environ.get("PEGASUS_EBENCH_DIR", "/tmp/pegasus_engine_bench")
    if any(b.startswith("tpu") for b in backends):
        if os.environ.get("PEGASUS_EBENCH_FAKE") == "sleep":
            time.sleep(3600)  # test hook: backend init wedges
        from pegasus_tpu.base.utils import open_device_backend

        device = open_device_backend()
    results = _RESULTS
    for backend in backends:
        results[backend] = run_lane(backend, root, n, value_size, n_files,
                                    reps)
        if backend.startswith("tpu"):
            results[backend]["device"] = device
        print(json.dumps(results[backend]), flush=True)
    tpu_lanes = [k for k in results if k.startswith("tpu")]
    if "cpu" in results and tpu_lanes:
        best = min(tpu_lanes, key=lambda k: results[k]["manual_compact_s"])
        cmp = {
            "metric": (f"engine manual_compact speedup, device lanes on "
                       f"jax platform {device['platform']}, vs cpu "
                       f"({n} records)"),
            "value": round(results["cpu"]["manual_compact_s"]
                           / results[best]["manual_compact_s"], 3),
            "unit": "x",
            "best_lane": best,
            "byte_equal": all(results["cpu"]["digest"] == results[k]["digest"]
                              for k in tpu_lanes),
        }
        print(json.dumps(cmp), flush=True)
    _PRINTED_FINAL = True
    shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
