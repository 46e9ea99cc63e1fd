"""pegasus_bench equivalent: fillrandom + full compaction, cpu vs tpu backend.

Mirrors the reference harness shape (src/test/bench_test: fillrandom_pegasus
then manual compact; BASELINE.json north star = fillrandom+compact wall-clock
vs CPU) on this build's engine: generate N records across K overlapping runs,
flush-sort each run (an L0 state — untimed, as in the reference where bench
fills then separately times manual_compact), then run the full
merge+dedup+TTL-filter compaction on both backends:

  cpu: vectorized numpy k-way merge (searchsorted ranks over memcmp-ordered
       packed keys — a strong CPU implementation, deliberately NOT the slow
       lexsort strawman; stand-in for CPU RocksDB until the C++ harness lands)
  tpu: JAX bitonic-merge networks on the real chip. Key columns are
       device-resident (uploaded at flush, the engine's architecture), so the
       timed path is kernel + survivor materialization (device value gather
       overlapped with host key gather, or the host fused gather — whichever
       this box measures faster).

Both lanes share the fill recipe (seed-deterministic) and are timed from
merge start to fully materialized output block; outputs are asserted
BYTE-IDENTICAL (sha256 across the process boundary).

Process architecture: a chip belongs to one process at a time, so the
parent NEVER imports jax; one child does backend init + the whole device
lane under a parent-enforced deadline (PEGASUS_BENCH_LANE_S), with
stdout/stderr on files. On timeout the child gets SIGTERM, a grace
period, then SIGKILL.

A bench that cannot produce its number FAILS: the reason goes to stderr,
the exit code is non-zero and stdout carries no result line. The device
lane refuses any platform but a TPU unless JAX_PLATFORMS names one
explicitly (JAX_PLATFORMS=cpu rehearses the lane on XLA:CPU), and then the
metric's name says which platform it timed.

Prints ONE json line:
  {"metric": ..., "value": speedup, "unit": "x", "vs_baseline": ...}
vs_baseline is speedup / 1.0 (the CPU path IS the measured baseline; the
reference publishes no in-repo numbers).

Env knobs: PEGASUS_BENCH_N (records, default 10_000_000), PEGASUS_BENCH_VALUE
(user bytes per value, default 100), PEGASUS_BENCH_RUNS (L0 runs, default 4),
PEGASUS_BENCH_REPS (timed reps, default 3), PEGASUS_BENCH_LANE_S (TPU child
deadline, default 360), PEGASUS_BENCH_DEADLINE_S (in-process per-attempt
lane-guard deadline, default 0.7 * LANE_S so the stage-attributed abandon
undercuts the external kill), PEGASUS_BENCH_TIMEOUT_S (whole-bench
watchdog, default 600).
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

_RESULT_PRINTED = False
# the live lane child, so the watchdog backstop can stop it instead of
# leaking a process past the parent's exit
_LANE_STATE = {"proc": None}


def _emit(result: dict) -> None:
    global _RESULT_PRINTED
    # flag BEFORE printing: the watchdog thread checks it, and the reverse
    # order could let it fail a run whose result is already on stdout
    _RESULT_PRINTED = True
    print(json.dumps(result), flush=True)


def _fail_message(reason: str, detail: dict = None) -> None:
    """Why this bench produced no number — on STDERR. stdout carries
    results only, so a failed run can never be parsed as a measurement
    (the on-chip rule: a measurement path that finds no chip fails)."""
    print(f"bench FAILED: {reason}", file=sys.stderr, flush=True)
    if detail:
        print(json.dumps(detail), file=sys.stderr, flush=True)


def _abort(reason: str, detail: dict = None):
    _fail_message(reason, detail)
    sys.exit(1)


def _host_info() -> dict:
    """Host-contention attribution (a 2.9s -> 7.8s CPU-lane regression
    was once only guessable as host contention): loadavg + core
    count recorded in every BENCH detail; the per-stage process_time vs
    wall split rides in the trace summaries (runtime/tracing.py cpu_s —
    cpu_s >> s means parallel threads worked under the span, s >> cpu_s
    with high loadavg means the host starved the stage)."""
    try:
        la = [round(x, 2) for x in os.getloadavg()]
    except (AttributeError, OSError):
        la = None
    return {"cpu_count": os.cpu_count(), "loadavg": la}


def _bench_params():
    """(n_total, n_runs, value_size, reps) — single source for main(), the
    child lane and the metric name."""
    return (int(os.environ.get("PEGASUS_BENCH_N", 10_000_000)),
            int(os.environ.get("PEGASUS_BENCH_RUNS", 4)),
            int(os.environ.get("PEGASUS_BENCH_VALUE", 100)),
            int(os.environ.get("PEGASUS_BENCH_REPS", 3)))


def _metric_name(n_total, n_runs, value_size, platform: str) -> str:
    """The metric is named for the platform the device lane ran on: only
    a tpu run may carry the tpu name."""
    lane = ("tpu-backend compaction" if platform == "tpu" else
            f"device lane rehearsed on jax platform {platform} (not a "
            f"device number)")
    return (f"fillrandom+compact: {lane} speedup vs cpu "
            f"backend ({n_total} records, {n_runs} runs, value={value_size}B)")


def _enable_compile_cache():
    from pegasus_tpu.base.utils import enable_compile_cache

    enable_compile_cache()


def make_run(n: int, value_size: int, seed: int, key_space: int) -> "KVBlock":
    """Vectorized fillrandom: n records, 16B hashkey + 8B sortkey, v2 values,
    ~10% with TTL already expired, ~5% tombstones (fractions overridable:
    PEGASUS_BENCH_TTL_FRAC / PEGASUS_BENCH_DEL_FRAC — the TTL-expiring
    compaction scenario of BASELINE.json is TTL_FRAC=0.5+). Seed-deterministic:
    the TPU child regenerates the identical fill from the same seeds."""
    from pegasus_tpu.engine.block import KVBlock

    ttl_frac = float(os.environ.get("PEGASUS_BENCH_TTL_FRAC", 0.10))
    del_frac = float(os.environ.get("PEGASUS_BENCH_DEL_FRAC", 0.05))

    rng = np.random.default_rng(seed)
    klen = 2 + 16 + 8
    keys = np.zeros((n, klen), dtype=np.uint8)
    keys[:, 0], keys[:, 1] = 0, 16  # u16 BE hashkey len
    # hashkeys drawn from a bounded space so runs overlap (dedup work exists)
    hk_ids = rng.integers(0, key_space, size=n)
    digits = np.zeros((n, 16), np.uint8)
    v = hk_ids.copy()
    for j in range(15, 7, -1):
        digits[:, j] = 48 + (v % 10)
        v //= 10
    digits[:, :8] = np.frombuffer(b"userhash", dtype=np.uint8)
    keys[:, 2:18] = digits
    keys[:, 18:26] = rng.integers(0, 256, size=(n, 8), dtype=np.uint8)

    vlen = 13 + value_size  # v2 header + payload
    vals = rng.integers(0, 256, size=(n, vlen), dtype=np.uint8)
    vals[:, 0] = 0x82
    expire = np.zeros(n, np.uint32)
    with_ttl = rng.random(n) < ttl_frac
    expire[with_ttl] = rng.integers(1, 50, size=int(with_ttl.sum()), dtype=np.uint32)
    vals[:, 1] = (expire >> 24).astype(np.uint8)
    vals[:, 2] = (expire >> 16).astype(np.uint8)
    vals[:, 3] = (expire >> 8).astype(np.uint8)
    vals[:, 4] = expire.astype(np.uint8)
    vals[:, 5:13] = 0
    deleted = rng.random(n) < del_frac

    from pegasus_tpu.base.crc64 import crc64_batch

    hashes = crc64_batch(keys.reshape(-1), np.arange(n, dtype=np.int64) * klen + 2,
                         np.full(n, 16, np.int64))
    return KVBlock(
        key_arena=keys.reshape(-1),
        key_off=np.arange(n, dtype=np.int64) * klen,
        key_len=np.full(n, klen, np.int32),
        val_arena=vals.reshape(-1),
        val_off=np.arange(n, dtype=np.int64) * vlen,
        val_len=np.full(n, vlen, np.int32),
        expire_ts=expire,
        hash32=(hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        deleted=deleted,
    )


def presort_run(block):
    """Flush: order the raw fill by key (untimed; L0 SSTs are born sorted)."""
    from pegasus_tpu.ops.packing import pack_key_prefixes, pack_sbytes

    w = 7  # 26-byte keys -> ceil(26/4)
    pref = pack_key_prefixes(block.key_arena, block.key_off, block.key_len, w)
    sb = pack_sbytes([pref[:, j] for j in range(w)],
                     block.key_len.astype(np.uint32))
    order = np.argsort(sb, kind="stable")
    # drop within-run duplicate keys (LSM invariant; first writer wins)
    sb_sorted = sb[order]
    uniq = np.ones(len(order), dtype=bool)
    uniq[1:] = sb_sorted[1:] != sb_sorted[:-1]
    return block.gather(order[uniq])


def _fill(n_total, n_runs, value_size):
    """-> (runs, fill_s). Shared verbatim by parent (CPU lane) and the TPU
    child; determinism across the two processes is what lets byte equality
    be checked by hash."""
    t0 = time.perf_counter()
    runs = [presort_run(make_run(n_total // n_runs, value_size, seed=s,
                                 key_space=max(1, n_total // 2)))
            for s in range(n_runs)]
    return runs, time.perf_counter() - t0


def _out_digest(block) -> dict:
    return {
        "n_out": int(block.n),
        "key_sha": hashlib.sha256(block.key_arena).hexdigest(),
        "val_sha": hashlib.sha256(block.val_arena).hexdigest(),
    }


def _lane_deadline_s() -> float:
    """Per-attempt in-process deadline for the guarded device lane. It
    must undercut PEGASUS_BENCH_LANE_S by a real margin: the parent's
    timer covers the whole child lifetime (init + fill + prep too), so an
    equal deadline would always lose the race to the external SIGTERM and
    the stage-attributed abandon would never fire. The parent kill stays
    the backstop for wedges outside the guarded merge itself."""
    v = os.environ.get("PEGASUS_BENCH_DEADLINE_S")
    if v:
        return float(v)
    # strictly under lane_s even for tiny operator-set budgets, or the
    # external SIGTERM always wins and this deadline is dead code
    lane_s = float(os.environ.get("PEGASUS_BENCH_LANE_S", 360))
    return max(5.0, min(lane_s * 0.7, lane_s - 10.0))


def _lane(backend, packed_in, concat, fargs, reps, dev_vals=None):
    """Timed compaction lane: merge + survivor materialization, best of
    reps (first rep is jit-compile warmup). dev_vals switches the device
    lane's materialization to HBM-resident value rows (downloaded as one
    block, overlapped with the host key gather).

    The device lane runs under the lane guard with fallback DISABLED: a
    bench must report the device number or fail loudly — a silent cpu
    fallback would publish a cpu time as "tpu". Retries/abandons land in
    the guard's counters, exported as the JSON line's detail.lane."""
    from pegasus_tpu.ops.compact import (gather_device_survivors,
                                         materialize_device_survivors)

    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    best, out, split = float("inf"), None, {}
    for _ in range(reps + 1):
        t0 = time.perf_counter()
        if hasattr(backend, "survivors_device"):
            from pegasus_tpu.runtime.lane_guard import LANE_GUARD

            def _attempt():
                dev_idx, cnt = backend.survivors_device(packed_in, *fargs)
                t_merge = time.perf_counter()
                if dev_vals is not None:
                    # values come off the device; host gathers keys+aux
                    o = materialize_device_survivors(concat, dev_vals,
                                                     dev_idx, cnt)
                else:
                    # index download overlaps the memcpy-bound arena gather
                    o = gather_device_survivors(concat, dev_idx, cnt)
                return t_merge, o

            t1, out = LANE_GUARD.run(_attempt, None, op="bench-lane",
                                     deadline_s=_lane_deadline_s())
        else:
            surv = backend.survivors(packed_in, *fargs)
            t1 = time.perf_counter()
            with COMPACT_TRACER.span("gather", records=len(surv)):
                out = concat.gather(surv)
        total = time.perf_counter() - t0
        if total < best:
            best = total
            split = {"merge_s": round(t1 - t0, 3),
                     "gather_s": round(total - (t1 - t0), 3)}
    return best, out, split


def _clear_pipeline_caches():
    from pegasus_tpu.ops import compact as C

    C._compiled_pipeline.cache_clear()
    C._compiled_pipeline_cached.cache_clear()
    C._compiled_pipeline_cached_padded.cache_clear()


def _tpu_lanes(backend, prep, concat, fargs, reps):
    """Time BOTH device materialization strategies (host fused gather vs
    HBM-resident value rows) and return the best, with the loser's numbers
    kept in the split detail — the winner depends on the host's memcpy
    speed vs the device download bandwidth, which only a measurement on
    the actual box can settle. On real TPU hardware, additionally TRIAL
    the Pallas merge kernel self-validatingly (byte-equality against the
    XLA lane's output; any lowering failure is recorded, not fatal) —
    Pallas stays off by default; turning it on is a measured perf change."""
    import jax

    from pegasus_tpu.ops.compact import prepare_values

    tpu_s, out, split = _lane(backend, prep, concat, fargs, reps)
    split = dict(split, gather_path="host")
    best_dev_vals = None
    dev_vals = prepare_values(concat)  # flush-time upload: untimed
    if dev_vals is not None:
        s_b, out_b, split_b = _lane(backend, prep, concat, fargs, reps,
                                    dev_vals=dev_vals)
        if s_b < tpu_s:
            alt = {"path": "host", "tpu_compact_s": round(tpu_s, 3),
                   **{k: v for k, v in split.items() if k != "gather_path"}}
            tpu_s, out = s_b, out_b
            best_dev_vals = dev_vals
            split = dict(split_b, gather_path="device-values", alt=alt)
        else:
            split["alt"] = {"path": "device-values",
                            "tpu_compact_s": round(s_b, 3), **split_b}
    if (jax.default_backend() == "tpu"
            and os.environ.get("PEGASUS_PALLAS") is None):
        os.environ["PEGASUS_PALLAS"] = "1"
        _clear_pipeline_caches()
        try:
            s_p, out_p, split_p = _lane(backend, prep, concat, fargs, reps,
                                        dev_vals=best_dev_vals)
            if (out_p.n != out.n
                    or not np.array_equal(out_p.key_arena, out.key_arena)
                    or not np.array_equal(out_p.val_arena, out.val_arena)):
                split["pallas"] = {"status": "BYTE-MISMATCH vs xla lane",
                                   "tpu_compact_s": round(s_p, 3)}
            elif s_p < tpu_s:
                # keep the gather-strategy comparison from the xla pass:
                # the JSON line must still answer host-vs-device-values
                xla_alt = {"path": "xla", "tpu_compact_s": round(tpu_s, 3)}
                if "alt" in split:
                    xla_alt["alt"] = split["alt"]
                split = dict(split_p, gather_path=split["gather_path"],
                             kernel="pallas", alt=xla_alt)
                tpu_s, out = s_p, out_p
            else:
                split["pallas"] = {"status": "validated, slower",
                                   "tpu_compact_s": round(s_p, 3), **split_p}
        except Exception as e:  # noqa: BLE001 - lowering failure is data
            split["pallas"] = {"status": f"failed: {type(e).__name__}: "
                                         f"{str(e)[:200]}"}
        finally:
            os.environ.pop("PEGASUS_PALLAS", None)
            _clear_pipeline_caches()
    return tpu_s, out, split


def _compact_opts():
    from pegasus_tpu.ops.compact import CompactOptions

    opts = CompactOptions(backend="tpu", now=100, bottommost=True,
                          runs_sorted=True)
    return opts, (opts.now, opts.pidx, opts.partition_mask, True, True)


def tpu_lane_main():
    """Child process: backend init + full device lane (one process, one
    chip). Prints ONE json line with timings and the output digest; the
    parent compares digests for byte equality.

    The device-health watchdog heartbeats to PEGASUS_BENCH_STATUS_FILE
    (set by the parent) for the whole lane: if the device wedges and the
    parent has to stop this child, the parent reads the heartbeat and
    reports WHICH stage wedged (device_init / pack / h2d / device /
    gather) instead of a bare timeout."""
    from pegasus_tpu.ops.device_watchdog import WATCHDOG
    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    WATCHDOG.status_path = os.environ.get("PEGASUS_BENCH_STATUS_FILE")
    # heartbeat-only until the platform is up: a probe starved behind a
    # healthy-but-slow backend init would report a false wedge. A wedge
    # DURING init is still attributed — the heartbeat keeps writing
    # open_stages, and the parent reads the open device_init span
    WATCHDOG.probes_armed = False
    WATCHDOG.start()

    n_total, n_runs, value_size, reps = _bench_params()
    t_init = time.perf_counter()
    with COMPACT_TRACER.span("device_init"):
        from pegasus_tpu.base.utils import open_device_backend

        # refuses a platform that is neither a TPU nor explicitly asked for
        device = open_device_backend()
    init_s = time.perf_counter() - t_init
    WATCHDOG.probes_armed = True  # platform bound: liveness probes are safe
    print(f"tpu-lane: backend up in {init_s:.1f}s ({device})",
          file=sys.stderr, flush=True)

    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.ops.compact import TpuBackend, pack_runs

    host_start = _host_info()
    runs, fill_s = _fill(n_total, n_runs, value_size)
    opts, fargs = _compact_opts()
    proc_t0 = time.process_time()
    with COMPACT_TRACER.session() as sess:
        packed = pack_runs(runs, opts, need_sbytes=False)
        concat = KVBlock.concat(runs)
        del runs
        backend = TpuBackend()
        prep = backend.prepare(packed)  # device residency: flush-time, untimed
        tpu_s, out, split = _tpu_lanes(backend, prep, concat, fargs, reps)
    from pegasus_tpu.runtime.lane_guard import LANE_GUARD

    result = {"ok": True, "tpu_s": tpu_s, "split": split,
              "device": device, "init_s": round(init_s, 1),
              "fill_s": round(fill_s, 3), "trace": sess.summary(),
              "process_s": round(time.process_time() - proc_t0, 3),
              "host": {"start": host_start, "end": _host_info()},
              # lane-guard totals: a run with fallbacks/abandons > 0 can
              # never silently masquerade as a clean device number
              "lane": LANE_GUARD.state()}
    result.update(_out_digest(out))
    print(json.dumps(result), flush=True)


def _run_tpu_lane_child(lane_timeout_s: float):
    """Spawn + babysit the device lane child. -> (result_dict | None,
    reason, watchdog status | None).

    Child stdout/stderr go to temp FILES (a pipe nobody drains could
    block it). The child's watchdog heartbeats its stage/liveness state
    to a status FILE the parent reads on timeout — a wedged lane reports
    the stage it wedged at instead of only the generic message."""
    fake = os.environ.get("PEGASUS_BENCH_FAKE_LANE")
    status_f = tempfile.NamedTemporaryFile(prefix="bench_lane_",
                                           suffix=".status", delete=False)
    status_f.close()
    child_env = dict(os.environ, PEGASUS_BENCH_STATUS_FILE=status_f.name)
    if fake == "sleep":  # test hook: simulates a wedge after a healthy start
        cmd = [sys.executable, "-c", "import time; time.sleep(3600)"]
    elif fake == "wedge":  # test hook: a wedge AFTER the watchdog captured
        # the stage — exercises the parent's status-file read path
        cmd = [sys.executable, "-c",
               "import json, os, time; json.dump("
               "{'wedged_at_stage': 'device', 'last_ok': time.time()},"
               " open(os.environ['PEGASUS_BENCH_STATUS_FILE'], 'w'));"
               " time.sleep(3600)"]
    elif fake == "crash":  # test hook: simulates backend-init death
        cmd = [sys.executable, "-c",
               "import sys; print('boom', file=sys.stderr); sys.exit(7)"]
    else:
        cmd = [sys.executable, os.path.abspath(__file__), "--tpu-lane"]
    out_f = tempfile.NamedTemporaryFile(prefix="bench_lane_", suffix=".out",
                                        delete=False)
    err_f = tempfile.NamedTemporaryFile(prefix="bench_lane_", suffix=".err",
                                        delete=False)
    with out_f, err_f:
        proc = subprocess.Popen(
            cmd, stdout=out_f, stderr=err_f, stdin=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=child_env)
        _LANE_STATE["proc"] = proc
        timed_out = False
        try:
            proc.wait(timeout=lane_timeout_s)
        except subprocess.TimeoutExpired:
            timed_out = True
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    with open(err_f.name, "r", errors="replace") as f:
        err_tail = " | ".join(f.read().strip().splitlines()[-3:])[-400:]
    with open(out_f.name, "r", errors="replace") as f:
        stdout = f.read()
    status = None
    try:
        with open(status_f.name, "r") as f:
            status = json.loads(f.read() or "null")
    except (OSError, ValueError):
        pass
    for name in (out_f.name, err_f.name, status_f.name):
        try:
            os.unlink(name)
        except OSError:
            pass
    result = None
    for line in stdout.strip().splitlines():
        if line.startswith("{"):
            try:
                result = json.loads(line)
            except ValueError:
                pass
    if result is not None and result.get("ok"):
        return result, "", status
    if timed_out:
        where = ""
        if status and status.get("wedged_at_stage"):
            where = f"; wedged at stage: {status['wedged_at_stage']}"
        elif status and status.get("open_stages"):
            open_all = [s for st in status["open_stages"].values() for s in st]
            if open_all:
                where = f"; last open stage: {open_all[-1]}"
        return None, (f"device lane exceeded {lane_timeout_s:.0f}s (wedged "
                      f"mid-init or mid-run){where}; child stopped"), status
    if proc.returncode != 0:
        return None, f"device lane died rc={proc.returncode}: {err_tail}", \
            status
    return None, ("device lane exited 0 but produced no result line: "
                  + err_tail), status


def _arm_watchdog():
    """Absolute backstop: the parent itself must never outlive the
    caller's budget even if some host-side step stalls. Fails the run
    (non-zero exit, no result line) after PEGASUS_BENCH_TIMEOUT_S
    (0 disables)."""
    import threading

    budget = int(os.environ.get("PEGASUS_BENCH_TIMEOUT_S", 600))
    if budget <= 0:
        return

    def boom():
        if _RESULT_PRINTED:
            os._exit(0)  # the result is out; only teardown stalled
        _fail_message(f"watchdog fired after {budget}s with no result")
        proc = _LANE_STATE["proc"]
        if proc is not None and proc.poll() is None:
            proc.kill()
        os._exit(1)

    t = threading.Timer(budget, boom)
    t.daemon = True
    t.start()


def _ycsb_params():
    """(records, ops, threads, partitions, value_size) for the serving
    bench — single source for the lane and the metric name."""
    return (int(os.environ.get("PEGASUS_BENCH_YCSB_RECORDS", 10_000)),
            int(os.environ.get("PEGASUS_BENCH_YCSB_OPS", 20_000)),
            int(os.environ.get("PEGASUS_BENCH_YCSB_THREADS", 8)),
            int(os.environ.get("PEGASUS_BENCH_YCSB_PARTITIONS", 32)),
            int(os.environ.get("PEGASUS_BENCH_VALUE", 100)))


def _ycsb_mix():
    """(mix letter, read fraction): PEGASUS_BENCH_YCSB_MIX selects the
    YCSB op mix — 'a' 50/50 read/update (default), 'b' 95/5,
    'c' 100/0 read-only, 'e' 95/5 short-scan/insert (the YCSB-E shape:
    the "read" is a bounded multi_get range under one hashkey). The
    read-heavy variants are the device-served read A/B workload, and 'e'
    the device-served RANGE-read one (run with PEGASUS_DEVICE_READS=1 vs
    0 against a tpu-backend onebox on hardware; see ROADMAP)."""
    m = (os.environ.get("PEGASUS_BENCH_YCSB_MIX", "a").strip().lower()
         or "a")
    return m, {"a": 0.5, "b": 0.95, "c": 1.0, "e": 0.95}.get(m, 0.5)


def _ycsb_metric_name() -> str:
    records, ops, threads, partitions, value_size = _ycsb_params()
    mix, read_frac = _ycsb_mix()
    pct = int(round(read_frac * 100))
    shape = "scan-insert" if mix == "e" else "read-update"
    return (f"YCSB-{mix.upper()} {pct}/{100 - pct} {shape} ops/sec "
            f"({records} records, "
            f"{ops} ops, {threads} threads, {partitions} partitions, "
            f"value={value_size}B)")


class ZipfKeys:
    """YCSB's quick-zipfian rank generator (Gray et al., SIGMOD '94
    "Quickly generating billion-record synthetic databases"): ranks over
    [0, n) with P(rank k) ~ 1/(k+1)^theta. The naive continuous inverse
    transform (`u ** (1/(1-theta))`) is NOT zipf — at theta=0.99 it puts
    ~91% of all picks on rank 0, so an ops/sec number produced with it
    measures one hot key on one partition instead of a skewed workload."""

    def __init__(self, n: int, theta: float = 0.99):
        self.n = n
        self.zetan = float(np.sum(1.0 / np.arange(1, n + 1) ** theta))
        self.zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1.0 - (2.0 / n) ** (1.0 - theta))
                    / (1.0 - self.zeta2 / self.zetan))

    def pick(self, rng) -> int:
        u = rng.random()
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < self.zeta2:
            return 1
        return min(self.n - 1,
                   int(self.n * (self.eta * u - self.eta + 1.0) ** self.alpha))


def _max_quantiles(dicts):
    """Collector-style merge of percentile dicts across partitions: the
    max per quantile (the worst partition bounds the fleet)."""
    out = {}
    for d in dicts:
        for q, v in d.items():
            out[q] = max(out.get(q, 0), v)
    return out


_YCSB_E_GROUP = 100  # sortkeys per hashkey in the mix='e' load shape


def _ycsb_load_and_run(box, records, n_ops, n_threads, value,
                       read_frac: float = 0.5, during=None,
                       tables=("ycsb",), scan_mix: bool = False):
    """Shared YCSB workload driver: load `records`, run the read/update
    mix (`read_frac` reads) from `n_threads` clients. -> stats dict (the
    sweep mode reruns this once per group count). `during`, when given,
    runs on its own thread WHILE the workers hammer the cluster (the
    consistency audit rides here: digests must match under concurrent
    load, not just at rest); its return value lands in stats["during"].
    With multiple `tables` the record budget splits evenly and each
    worker thread pins one table (tid % len(tables)) — the multi-tenant
    shape the per-table ledger breakdown attributes.

    scan_mix=True is the YCSB-E shape: records load as _YCSB_E_GROUP
    sortkeys per hashkey, the read op is a SHORT SCAN (bounded multi_get
    range from a random start sortkey, length uniform 1.._YCSB_E_GROUP —
    the device range-read path) and the write op an INSERT of a fresh
    row, latencies in bench.ycsb.{scan,insert}_latency_us."""
    from pegasus_tpu.client import MetaResolver, PegasusClient
    from pegasus_tpu.runtime.perf_counters import counters
    from pegasus_tpu.runtime.tasking import spawn_thread

    tables = tuple(tables) or ("ycsb",)
    per_records = records if len(tables) == 1 else max(1,
                                                      records // len(tables))

    def load_key(i):
        if scan_mix:
            return (b"user%09d" % (i // _YCSB_E_GROUP),
                    b"s%04d" % (i % _YCSB_E_GROUP))
        return b"user%012d" % i, b"f0"

    t0 = time.perf_counter()
    for table in tables:
        load_cli = PegasusClient(MetaResolver([box.meta_addr], table))
        for i in range(per_records):
            hk, sk = load_key(i)
            load_cli.set(hk, sk, value)
        load_cli.close()
    load_s = time.perf_counter() - t0

    errors = [0]
    read_lat = counters.percentile("bench.ycsb.read_latency_us")
    update_lat = counters.percentile("bench.ycsb.update_latency_us")
    scan_lat = counters.percentile("bench.ycsb.scan_latency_us")
    insert_lat = counters.percentile("bench.ycsb.insert_latency_us")
    zipf = ZipfKeys(per_records)

    def worker(tid):
        import random

        rng = random.Random(tid)
        cli = PegasusClient(MetaResolver([box.meta_addr],
                                         tables[tid % len(tables)]))
        inserts = 0
        for _ in range(n_ops // n_threads):
            pick = zipf.pick(rng)
            s = time.perf_counter()
            try:
                if scan_mix:
                    if rng.random() < read_frac:
                        hk = b"user%09d" % (pick // _YCSB_E_GROUP)
                        first = rng.randrange(_YCSB_E_GROUP)
                        cli.multi_get(
                            hk, None,
                            max_kv_count=rng.randint(1, _YCSB_E_GROUP),
                            start_sortkey=b"s%04d" % first)
                        scan_lat.set(int((time.perf_counter() - s) * 1e6))
                    else:
                        # fresh rows keyed per thread: inserts, not updates
                        cli.set(b"insert%03d" % tid, b"s%08d" % inserts,
                                value)
                        inserts += 1
                        insert_lat.set(int((time.perf_counter() - s) * 1e6))
                    continue
                k = b"user%012d" % pick
                if rng.random() < read_frac:
                    cli.get(k, b"f0")
                    read_lat.set(int((time.perf_counter() - s) * 1e6))
                else:
                    cli.set(k, b"f0", value)
                    update_lat.set(int((time.perf_counter() - s) * 1e6))
            except Exception:
                errors[0] += 1
        cli.close()

    threads = [spawn_thread(worker, t, daemon=False, start=False)
               for t in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    during_box = [None]
    during_thread = None
    if during is not None:
        def _run_during():
            try:
                during_box[0] = during()
            except Exception as e:  # noqa: BLE001 - report, don't crash
                during_box[0] = {"error": repr(e)}
        during_thread = spawn_thread(_run_during, daemon=False)
    for t in threads:
        t.join()
    run_s = time.perf_counter() - t0
    if during_thread is not None:
        during_thread.join()
    done_ops = n_threads * (n_ops // n_threads)
    return {
        "during": during_box[0],
        "ops_s": round(done_ops / run_s, 1),
        "run_s": round(run_s, 2),
        "load_s": round(load_s, 2),
        "load_ops_s": round(per_records * len(tables) / max(load_s, 1e-9), 1),
        "errors": errors[0],
        "client_latency_us": (
            {"scan": scan_lat.percentiles(),
             "insert": insert_lat.percentiles()} if scan_mix else
            {"read": read_lat.percentiles(),
             "update": update_lat.percentiles()}),
    }


def _ycsb_table_breakdown(meta_addr):
    """Per-table capacity attribution for the run (ISSUE 18): fold every
    node's `table-stats` ledger fragments into cluster-wide per-table
    series + the top-k ranking — the same merge the collector performs,
    driven here through the public remote-command surface so the bench
    exercises the wire path, not process-local state."""
    from pegasus_tpu.collector.cluster_doctor import ClusterCaller
    from pegasus_tpu.runtime.table_stats import fold_snapshots, top_k

    caller = ClusterCaller([meta_addr])
    try:
        state = caller.meta_state() or {}
        frags = []
        for addr, node in sorted((state.get("nodes") or {}).items()):
            if not node.get("alive", False):
                continue
            try:
                reply = json.loads(caller.remote_command(
                    addr, "table-stats", []))
            except Exception:  # noqa: BLE001 - attribution is best-effort
                continue
            if isinstance(reply, dict):
                frags.extend(v for v in reply.values() if isinstance(v, dict))
        folded = fold_snapshots(frags)
        return {"tables": folded, "top": top_k(folded)}
    finally:
        caller.close()


def _ycsb_group_sweep(groups_list):
    """PEGASUS_BENCH_YCSB_GROUPS=1,4: the partition-group scaling
    artifact. The SAME YCSB-A workload runs once per group count, each
    against a fresh onebox whose replica nodes serve through that many
    shared-nothing group-executor processes (groups=1 is the one-GIL
    ceiling, through the identical router architecture, so the sweep
    isolates the sharding win). Emits ONE json line whose value is the
    best ops/s and whose detail.sweep records every run + the host's
    contention state (per-group worker processes show up in loadavg)."""
    records, n_ops, n_threads, partitions, value_size = _ycsb_params()
    from tools._onebox import Onebox

    from pegasus_tpu.runtime.perf_counters import counters

    value = os.urandom(value_size)
    sweep = []
    for g in groups_list:
        # fresh latency windows per sweep entry: the percentile counters
        # are process-global and would otherwise blend the runs
        counters.remove("bench.ycsb.read_latency_us")
        counters.remove("bench.ycsb.update_latency_us")
        counters.remove("bench.ycsb.scan_latency_us")
        counters.remove("bench.ycsb.insert_latency_us")
        host_start = _host_info()
        box = Onebox("ycsb", partitions=partitions, serve_groups=g)
        try:
            stats = _ycsb_load_and_run(box, records, n_ops, n_threads, value,
                                       read_frac=_ycsb_mix()[1],
                                       scan_mix=_ycsb_mix()[0] == "e")
        finally:
            box.stop()
        entry = {"groups": g, "host": {"start": host_start,
                                       "end": _host_info()}}
        entry.update(stats)
        sweep.append(entry)
        print(f"ycsb sweep: groups={g} -> {stats['ops_s']} ops/s "
              f"(errors={stats['errors']})", file=sys.stderr, flush=True)
    base = next((e for e in sweep if e["groups"] == 1), None)
    best = max(sweep, key=lambda e: e["ops_s"])
    detail = {
        "sweep": sweep,
        "partitions": partitions, "threads": n_threads, "records": records,
        "scaling_vs_groups1": (round(best["ops_s"] / base["ops_s"], 3)
                               if base and base["ops_s"] else None),
    }
    _emit({
        "metric": (f"YCSB-{_ycsb_mix()[0].upper()} ops/sec, "
                   f"serve-group sweep groups="
                   f"{','.join(str(g) for g in groups_list)} "
                   f"({records} records, {n_ops} ops, {n_threads} threads, "
                   f"{partitions} partitions, value={value_size}B)"),
        "value": best["ops_s"],
        "unit": "ops/s",
        "vs_baseline": detail["scaling_vs_groups1"],
        "detail": detail,
    })


def ycsb_main():
    """PEGASUS_BENCH_MODE=ycsb: the serving-path lane — BASELINE.json's
    SECOND metric (YCSB-A 50/50 read/update over hash partitions), never
    recorded before this lane existed. Boots an in-process onebox (1 meta
    + 3 replica nodes over real sockets), loads N records, drives 50/50
    read/update from T client threads, and prints ONE json line with
    ops/sec, per-op-class p99 (from the server's <op>_latency_us
    percentiles), the plog group-size histogram and
    replica.prepare_latency_us (so the group-commit win is attributable),
    and a detail.host block (so host contention can't masquerade as a
    regression).

    PEGASUS_BENCH_YCSB_GROUPS=1,4 switches to the partition-group SWEEP:
    the same workload repeated per group count with the replica nodes
    split into that many shared-nothing executor processes
    (replication/serve_groups.py) — the scaling artifact for the
    serve-group work.

    The serving path is host-only: jax is pinned to the cpu platform
    BEFORE any engine import (a cpu-backend onebox; putting the served
    path on the device is ROADMAP S2)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _enable_compile_cache()

    groups_env = os.environ.get("PEGASUS_BENCH_YCSB_GROUPS", "").strip()
    if groups_env:
        groups_list = [max(1, int(x)) for x in groups_env.split(",") if x]
        _ycsb_group_sweep(groups_list)
        return

    records, n_ops, n_threads, partitions, value_size = _ycsb_params()
    from pegasus_tpu.runtime.perf_counters import counters

    from tools._onebox import Onebox

    host_start = _host_info()
    proc_t0 = time.process_time()
    mix, read_frac = _ycsb_mix()
    n_tables = max(1, int(os.environ.get("PEGASUS_BENCH_YCSB_TABLES", "1")))
    ycsb_tables = ["ycsb"] + [f"ycsb{i}" for i in range(2, n_tables + 1)]
    box = Onebox("ycsb", partitions=partitions)
    try:
        for extra in ycsb_tables[1:]:
            box.cluster.create(extra, partitions=partitions).close()
        value = os.urandom(value_size)

        def audit_under_load():
            """Decree-anchored consistency audit WHILE the workload runs
            (ISSUE 8 acceptance): every replica must digest identical
            state at identical decrees under concurrent YCSB traffic. A
            mismatch fails the whole bench run — a throughput number from
            a cluster serving divergent replicas is worthless."""
            from pegasus_tpu.collector.cluster_doctor import \
                run_cluster_audit

            return run_cluster_audit([box.meta_addr], apps=ycsb_tables,
                                     wait_s=20.0)

        stats = _ycsb_load_and_run(box, records, n_ops, n_threads, value,
                                   read_frac=read_frac,
                                   during=audit_under_load,
                                   tables=ycsb_tables,
                                   scan_mix=mix == "e")
        audit = stats.pop("during") or {}
        audit.pop("digests", None)  # per-node digests: bulky, summarized
        # zero mismatches is only a PASS when the audit actually compared
        # every partition — an errored or inconclusive audit must not
        # pose as validation (the mismatch gate below stays the only
        # run-failing condition, per the acceptance criterion)
        audit["conclusive"] = (not audit.get("error")
                               and audit.get("partitions", 0) > 0
                               and len(audit.get("ok", []))
                               == audit.get("partitions"))
        if not audit["conclusive"]:
            print(f"ycsb: consistency audit INCONCLUSIVE — zero "
                  f"mismatches is vacuous here: {audit}",
                  file=sys.stderr, flush=True)
        if audit.get("mismatches"):
            # flight recorder (ISSUE 12): capture the cluster's recorded
            # past NOW, while the onebox still serves — the failure
            # line below references the artifact instead of asking for a
            # re-reproduction
            try:
                from pegasus_tpu.collector.flight_recorder import RECORDER

                inc = RECORDER.capture(
                    [box.meta_addr],
                    reason=f"ycsb audit mismatch x{len(audit['mismatches'])}",
                    trigger="bench")
                audit["incident"] = {"id": inc["id"], "path": inc["path"]}
            except Exception as e:  # capture must not mask the mismatch
                print(f"ycsb: incident capture failed: {e!r}",
                      file=sys.stderr, flush=True)

        # ---- attribution: server-side latency percentiles per op class
        # (max across partitions, the collector's merge rule), the plog
        # group-commit histogram, and the prepare round's latency
        snap = counters.snapshot()
        server_lat = {}
        for op in ("get", "put"):
            dicts = [v for k, v in snap.items()
                     if k.startswith("app.") and k.endswith(f".{op}_latency_us")
                     and isinstance(v, dict)]
            if dicts:
                server_lat[op] = _max_quantiles(dicts)
        append_count = flush_count = 0
        for stub in box.cluster.stubs:
            for rep in stub._replicas.values():
                append_count += rep.plog.append_count
                flush_count += rep.plog.flush_count

        # ---- device-served reads attribution (ISSUE 7): per-stage read
        # spans, device probe totals and the read lane guard's state. The
        # same fallback-free rule the compaction bench applies: a run
        # whose read lane degraded (fallbacks/abandons > 0) must never
        # pass its device-read throughput off as a clean device number.
        from pegasus_tpu.runtime.lane_guard import READ_LANE_GUARD

        read_lane = READ_LANE_GUARD.state()
        reads_detail = {
            "mix": mix,
            "read_fraction": read_frac,
            "device": {
                "lookup_count": snap.get("read.device.lookup_count", 0),
                "keys": snap.get("read.device.keys", 0),
                "hits": snap.get("read.device.hits", 0),
            },
            "batch_size": snap.get("read.batch.size"),
            "spans": {k: v for k, v in snap.items()
                      if k.startswith("compact.stage.read.")},
            "lane": read_lane,
            "device_numbers_degraded": bool(
                read_lane["fallbacks"] or read_lane["deadline_abandons"]),
            # device-served RANGE reads (ISSUE 19): the scan path's own
            # totals + span durations and the same fallback-free rule —
            # a degraded lane's scan throughput is not a device number
            "scan": {
                "range": {k: snap.get("read.range." + k, 0)
                          for k in ("batch_count", "rows", "device_count",
                                    "host_count", "reverse_host_count")},
                "batch_size": snap.get("read.range.batch.size"),
                "spans": {k: v for k, v in snap.items()
                          if k.startswith("compact.stage.read.range")},
                "device_numbers_degraded": bool(
                    read_lane["fallbacks"]
                    or read_lane["deadline_abandons"]),
            },
        }
        result = {
            "metric": _ycsb_metric_name(),
            "value": stats["ops_s"],
            "unit": "ops/s",
            "vs_baseline": None,  # first recording of this BASELINE metric
            "detail": {
                "run_s": stats["run_s"],
                "load_s": stats["load_s"],
                "load_ops_s": stats["load_ops_s"],
                "errors": stats["errors"],
                "client_latency_us": stats["client_latency_us"],
                "server_latency_us": server_lat,
                "prepare_latency_us": snap.get("replica.prepare_latency_us"),
                "plog": {
                    "group_size": snap.get("plog.append.group_size"),
                    "append_count": append_count,
                    "flush_count": flush_count,
                    "group_ratio": round(
                        append_count / max(flush_count, 1), 3),
                },
                "partitions": partitions,
                "threads": n_threads,
                "records": records,
                "reads": reads_detail,
                # debt-driven admission control (ISSUE 10): whether the
                # graduated backpressure engaged during the run — a
                # nonzero delay count with zero rejects is the designed
                # "measured slowdown instead of a stall" shape
                "throttle": {
                    "debt_delay_count": snap.get(
                        "engine.throttle.debt_delay_count", 0),
                    "debt_reject_count": snap.get(
                        "engine.throttle.debt_reject_count", 0),
                    "debt_delay_ms": snap.get(
                        "engine.throttle.debt_delay_ms"),
                    "sched_deferred_count": snap.get(
                        "engine.compact.sched.deferred_count", 0),
                    "sched_urgent_count": snap.get(
                        "engine.compact.sched.urgent_count", 0),
                },
                "audit": audit,
                "cpu_process_s": round(time.process_time() - proc_t0, 3),
                "host": {"start": host_start, "end": _host_info()},
            },
        }
        if n_tables > 1:
            # multi-tenant breakdown (ISSUE 18): which table consumed the
            # run's capacity, folded from the nodes' per-table ledgers
            result["detail"]["tables"] = _ycsb_table_breakdown(box.meta_addr)
    finally:
        box.stop()
    if audit.get("mismatches"):
        # a digest mismatch under load is a CORRECTNESS failure: the
        # throughput number must not stand
        _abort(f"consistency audit FAILED: {len(audit['mismatches'])} digest "
              f"mismatch(es) — {audit['mismatches']}",
              detail=result["detail"])
    _emit(result)


# ----------------------------------------------------------- native A/B

# the native read data plane's attribution series (ISSUE 20): totals are
# deltas across each run so the A/B legs are cleanly separable
_NATIVE_COUNTERS = ("native.wave_count", "native.batch_frames",
                    "native.writev_count", "native.writev_bytes",
                    "native.sst_mmap_count")


def _native_metric_name() -> str:
    records, n_ops, n_threads, partitions, value_size = _ycsb_params()
    return (f"YCSB-C read-only ops/sec with PEGASUS_NATIVE=1 "
            f"(A/B vs =0 over mixes b/c/e + pipelined batch_get; "
            f"{records} records, {n_ops} ops, "
            f"{n_threads} threads, {partitions} partitions, "
            f"value={value_size}B)")


def _native_pipelined_leg(box, records, n_ops, n_threads, value):
    """Pipelined point-read leg for the native A/B. The YCSB mixes issue
    one blocking call per thread at a time, so no multi-frame wave ever
    reaches a connection and the binned-dispatch / vectored-reply stages
    sit idle (their counters flatline in both legs). This leg drives
    `PegasusClient.batch_get` — 32 keys per wave per thread — which is
    exactly the shape the C plane amortizes: the client send is one
    vectored sendmsg, the server bins the hot RPC_GET wave into one
    `on_get_batch`, and the replies leave as one vectored write.
    Self-checking: every read verifies the loaded value."""
    import random

    from pegasus_tpu.client import MetaResolver, PegasusClient
    from pegasus_tpu.runtime.tasking import spawn_thread

    wave_keys = 32
    load_cli = PegasusClient(MetaResolver([box.meta_addr], "ycsb"))
    for i in range(records):
        load_cli.set(b"user%012d" % i, b"f0", value)
    load_cli.close()

    done = [0] * n_threads
    errors = [0] * n_threads

    def worker(tid):
        rng = random.Random(0xBA7C4 + tid)
        cli = PegasusClient(MetaResolver([box.meta_addr], "ycsb"))
        try:
            per = n_ops // n_threads
            while done[tid] < per:
                items = [(b"user%012d" % rng.randrange(records), b"f0")
                         for _ in range(min(wave_keys, per - done[tid]))]
                vals = cli.batch_get(items)
                errors[tid] += sum(1 for v in vals if v != value)
                done[tid] += len(items)
        finally:
            cli.close()

    t0 = time.perf_counter()
    threads = [spawn_thread(worker, tid, daemon=False, start=False)
               for tid in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    run_s = time.perf_counter() - t0
    ops = sum(done)
    return {"ops_s": round(ops / max(run_s, 1e-9), 1),
            "run_s": round(run_s, 2), "errors": sum(errors)}


def native_main():
    """PEGASUS_BENCH_MODE=native: the native-read-data-plane A/B
    (ISSUE 20, BENCH_native artifact). The SAME YCSB workload runs with
    PEGASUS_NATIVE=0 (pure-Python frame loop, per-frame sendall, copying
    SST reads) then =1 (C binned dispatch waves, vectored sendmsg
    replies, zero-copy mmap SST sections) for each of the read-heavy
    mixes b (95/5), c (read-only) and e (short-scan), plus a PIPELINED
    batch_get leg that actually forms multi-frame waves (the blocking
    YCSB threads never do) — fresh onebox per leg, both legs
    byte-identical on the wire (test-enforced). Each side scores its
    best of PEGASUS_BENCH_NATIVE_REPS interleaved reps (a discarded
    warmup leg eats the jit compiles first). Emits ONE
    json line: value = mix c's native-on ops/s, vs_baseline = mix c's
    on/off ratio, detail.mixes the full grid with per-stage native.*
    counter deltas attributing where the native plane actually ran.
    Host-only (JAX_PLATFORMS=cpu)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _enable_compile_cache()
    records, n_ops, n_threads, partitions, value_size = _ycsb_params()
    from pegasus_tpu.runtime.perf_counters import counters

    from tools._onebox import Onebox

    host_start = _host_info()
    value = os.urandom(value_size)
    prior = os.environ.get("PEGASUS_NATIVE")
    reps = int(os.environ.get("PEGASUS_BENCH_NATIVE_REPS", 3))
    mixes = {}

    def run_leg(mix, nat):
        os.environ["PEGASUS_NATIVE"] = nat
        # fresh latency windows per leg: the percentile counters
        # are process-global and would otherwise blend the runs
        counters.remove("bench.ycsb.read_latency_us")
        counters.remove("bench.ycsb.update_latency_us")
        counters.remove("bench.ycsb.scan_latency_us")
        counters.remove("bench.ycsb.insert_latency_us")
        base = {name: counters.rate(name).total()
                for name in _NATIVE_COUNTERS}
        box = Onebox("ycsb", partitions=partitions)
        try:
            if mix == "pipelined":
                stats = _native_pipelined_leg(
                    box, records, n_ops, n_threads, value)
            else:
                read_frac = {"b": 0.95, "c": 1.0, "e": 0.95}[mix]
                stats = _ycsb_load_and_run(
                    box, records, n_ops, n_threads, value,
                    read_frac=read_frac, scan_mix=mix == "e")
        finally:
            box.stop()
        leg = {
            "ops_s": stats["ops_s"],
            "run_s": stats["run_s"],
            "errors": stats["errors"],
            "native_counters": {
                name: counters.rate(name).total() - base[name]
                for name in _NATIVE_COUNTERS},
        }
        if "client_latency_us" in stats:
            leg["client_latency_us"] = stats["client_latency_us"]
        print(f"native A/B: mix={mix} PEGASUS_NATIVE={nat} -> "
              f"{stats['ops_s']} ops/s (errors={stats['errors']})",
              file=sys.stderr, flush=True)
        return leg

    try:
        # discarded warmup leg: the first onebox in a process eats the
        # jit compiles and thread-pool spin-up; neither side should
        run_leg("c", "0")
        for mix in ("b", "c", "e", "pipelined"):
            # identical legs vary ±25% on a loaded 1-cpu host, so a
            # single-shot A/B is noise: interleave off/on reps (drift
            # hits both sides alike) and score each side by its best
            # rep — the run least disturbed by the host
            legs = {"0": [], "1": []}
            for _ in range(reps):
                for nat in ("0", "1"):
                    legs[nat].append(run_leg(mix, nat))
            entry = {}
            for nat in ("0", "1"):
                best = max(legs[nat], key=lambda leg: leg["ops_s"])
                best["rep_ops_s"] = [leg["ops_s"] for leg in legs[nat]]
                entry["on" if nat == "1" else "off"] = best
            entry["ratio"] = round(
                entry["on"]["ops_s"] / max(entry["off"]["ops_s"], 1e-9), 3)
            mixes[mix] = entry
    finally:
        if prior is None:
            os.environ.pop("PEGASUS_NATIVE", None)
        else:
            os.environ["PEGASUS_NATIVE"] = prior
    _emit({
        "metric": _native_metric_name(),
        "value": mixes["c"]["on"]["ops_s"],
        "unit": "ops/s",
        "vs_baseline": mixes["c"]["ratio"],
        "detail": {
            "mixes": mixes,
            "records": records, "ops": n_ops, "threads": n_threads,
            "partitions": partitions, "value_size": value_size,
            "host": {"start": host_start, "end": _host_info()},
        },
    })


def _learn_params():
    """(records, value_size) for PEGASUS_BENCH_MODE=learn — single
    source for the lane and the metric name."""
    return (int(os.environ.get("PEGASUS_BENCH_LEARN_RECORDS", 20_000)),
            int(os.environ.get("PEGASUS_BENCH_VALUE", 100)))


def _learn_metric_name() -> str:
    records, value_size = _learn_params()
    return (f"learn ship: monolithic vs streamed-delta bytes ratio "
            f"({records} records, value={value_size}B)")


def learn_main():
    """PEGASUS_BENCH_MODE=learn: the block-shipped learning artifact
    (ISSUE 13) — wall clock + shipped bytes for the three ways a replica
    can be (re-)seeded at N records, all in-process on CPU:

      * monolithic: the legacy whole-state copy (every checkpoint file
        read into memory and shipped, learner rebuilt from scratch);
      * full ship:  the streaming block plane, learner starting empty
        (same bytes as monolithic, but chunked/resumable/pinned);
      * delta ship: the streaming plane re-learning a learner that
        already holds the SSTs (the balancer-move/restart case the delta
        handshake exists for) after a small write burst on the primary;
      * replay:     log-replay-only catch-up of the same history — the
        baseline the ship path replaces for bulk state.

    Every learn's engine digest is compared against the primary at equal
    committed decrees (a transfer that loses bytes must fail the bench,
    not report a speed). One JSON line; failure semantics match the
    YCSB mode."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _enable_compile_cache()
    import shutil
    import tempfile

    from pegasus_tpu.base.utils import epoch_now
    from pegasus_tpu.engine import EngineOptions
    from pegasus_tpu.engine.server_impl import RPC_MULTI_PUT
    from pegasus_tpu.replication.replica import GroupView, Replica
    from pegasus_tpu.rpc import messages as rpc_msg
    from pegasus_tpu.runtime.perf_counters import counters

    records, value_size = _learn_params()
    host_start = _host_info()
    tmp = tempfile.mkdtemp(prefix="pegasus_learn_bench_")
    # small memtables so the loaded state lands in SSTs (the thing the
    # block plane ships); cpu backend end to end — no chip needed
    # to measure the replay-vs-ship win
    opts = lambda: EngineOptions(backend="cpu", memtable_bytes=256 << 10)  # noqa: E731
    reps = []

    def open_replica(name):
        r = Replica(name, os.path.join(tmp, name), options=opts(), quorum=1)
        reps.append(r)
        return r

    def ship_totals():
        return {k: counters.rate(f"learn.ship.{k}").total()
                for k in ("blocks", "bytes", "delta_skipped_blocks")}

    try:
        prim = open_replica("prim")
        prim.assume_view(GroupView(1, "prim", []))
        value = os.urandom(value_size)
        t0 = time.perf_counter()
        per = 100
        for base in range(0, records, per):
            kvs = [rpc_msg.KeyValue(b"s%08d" % i, value)
                   for i in range(base, min(base + per, records))]
            prim.client_write(RPC_MULTI_PUT, rpc_msg.MultiPutRequest(
                hash_key=b"h%05d" % (base % 97), kvs=kvs))
        load_s = time.perf_counter() - t0
        prim.server.engine.flush()
        now = epoch_now()

        def run_learn(learner, peer):
            before, t0 = ship_totals(), time.perf_counter()
            learner.learn_from(peer)
            after = ship_totals()
            ld = learner.server.engine.state_digest(now=now)
            pd = prim.server.engine.state_digest(now=now)
            return {
                "wall_s": round(time.perf_counter() - t0, 3),
                "bytes": after["bytes"] - before["bytes"],
                "blocks": after["blocks"] - before["blocks"],
                "delta_skipped_blocks": (after["delta_skipped_blocks"]
                                         - before["delta_skipped_blocks"]),
                "digest_match": (ld["digest"] == pd["digest"]
                                 and learner.last_committed
                                 == prim.last_committed),
            }

        class _MonolithicPeer:
            """Peer exposing ONLY the legacy surface, so learn_from
            takes the monolithic path against the same primary."""

            def fetch_learn_state(self):
                return prim.fetch_learn_state()

        mono = run_learn(open_replica("mono"), _MonolithicPeer())
        streamer = open_replica("full")
        full = run_learn(streamer, prim)
        # the delta case: a small burst on the primary, then re-learn
        # the SAME learner — it already holds (almost) every SST
        burst = max(1, records // 100)
        for base in range(0, burst, per):
            kvs = [rpc_msg.KeyValue(b"d%08d" % i, value)
                   for i in range(base, min(base + per, burst))]
            prim.client_write(RPC_MULTI_PUT, rpc_msg.MultiPutRequest(
                hash_key=b"hd%04d" % (base % 97), kvs=kvs))
        prim.server.engine.flush()
        delta = run_learn(streamer, prim)

        # replay-only catch-up baseline: the same history applied
        # mutation by mutation through the prepare path
        replayer = open_replica("replay")
        t0 = time.perf_counter()
        window, replayed = [], 0
        for m in prim.plog.replay(0):
            window.append(m)
            replayed += 1
            if len(window) >= 64:
                replayer.on_prepare_batch(prim.ballot, window,
                                          window[-1].decree)
                window = []
        if window:
            replayer.on_prepare_batch(prim.ballot, window,
                                      window[-1].decree)
        replay = {"wall_s": round(time.perf_counter() - t0, 3),
                  "mutations": replayed}
        # NOTE the honest asymmetry: after plog GC only the tail is
        # replayable at all — this baseline exists because the primary
        # here still holds its full log
        ratio = round(mono["bytes"] / max(delta["bytes"], 1), 2)
        detail = {
            "records": records, "value_bytes": value_size,
            "load_s": round(load_s, 2),
            "monolithic": mono, "full_ship": full, "delta_ship": delta,
            "replay_catch_up": replay,
            "bytes_ratio_mono_over_delta": ratio,
            "host": {"start": host_start, "end": _host_info()},
        }
        if not (mono["digest_match"] and full["digest_match"]
                and delta["digest_match"]):
            _abort("post-learn digest mismatch — a learn path lost bytes",
                  detail=detail)
        _emit({"metric": _learn_metric_name(), "value": ratio, "unit": "x",
               "vs_baseline": None, "detail": detail})
    finally:
        for r in reps:
            try:
                r.close()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _offload_params():
    """(records, runs, value_size) for PEGASUS_BENCH_MODE=offload."""
    return (int(os.environ.get("PEGASUS_BENCH_OFFLOAD_RECORDS", 200_000)),
            4, int(os.environ.get("PEGASUS_BENCH_VALUE", 100)))


def _offload_metric_name() -> str:
    records, n_runs, value_size = _offload_params()
    return (f"compaction offload: remote-vs-local wall ratio "
            f"({records} records, {n_runs} runs, value={value_size}B)")


def offload_main():
    """PEGASUS_BENCH_MODE=offload: the rack-scale compaction-offload
    artifact (ISSUE 14) — the same merge run locally on cpu and through
    an in-process CompactOffloadService over real sockets, all on CPU
    (no chip needed): wall clock for both lanes, bytes shipped and
    fetched, and the per-stage breakdown (offload.ship / offload.merge /
    offload.fetch spans). Byte identity between the lanes is asserted —
    a transfer that changes bytes must fail the bench, not report a
    speed — and a round the lane guard had to serve via the LOCAL cpu
    fallback fails the run (the number would not be an offload
    measurement). One JSON line, learn-mode semantics."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _enable_compile_cache()
    import shutil
    import tempfile

    from pegasus_tpu.ops.compact import CompactOptions, compact_blocks
    from pegasus_tpu.replication.compact_offload import (
        OFFLOAD_LANE_GUARD, CompactOffloadService, offload_compact_blocks)
    from pegasus_tpu.runtime.perf_counters import counters
    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    records, n_runs, value_size = _offload_params()
    host_start = _host_info()
    runs, fill_s = _fill(records, n_runs, value_size)
    opts = CompactOptions(backend="cpu", now=100, bottommost=True,
                          runs_sorted=True)
    tmp = tempfile.mkdtemp(prefix="pegasus_offload_bench_")
    svc = None
    try:
        t0 = time.perf_counter()
        local = compact_blocks(runs, opts)
        local_s = time.perf_counter() - t0
        local_digest = _out_digest(local.block)

        svc = CompactOffloadService(tmp, backend="cpu").start()
        OFFLOAD_LANE_GUARD.reset()

        def totals():
            return {k: counters.rate(f"offload.client.{k}").total()
                    for k in ("ship_bytes", "fetch_bytes", "ship_blocks",
                              "skipped_blocks")}

        before = totals()
        with COMPACT_TRACER.session() as sess:
            t0 = time.perf_counter()
            remote = offload_compact_blocks(runs, opts, svc.address,
                                            tenant="bench")
            offload_s = time.perf_counter() - t0
        after = totals()
        remote_digest = _out_digest(remote.block)
        lane = OFFLOAD_LANE_GUARD.state()
        detail = {
            "records": records, "n_runs": n_runs,
            "value_bytes": value_size, "fill_s": round(fill_s, 2),
            "local_compact_s": round(local_s, 3),
            "offload_compact_s": round(offload_s, 3),
            "shipped_bytes": after["ship_bytes"] - before["ship_bytes"],
            "fetched_bytes": after["fetch_bytes"] - before["fetch_bytes"],
            "shipped_runs": after["ship_blocks"] - before["ship_blocks"],
            "service": svc.status(),
            "lane": lane,
            "trace": sess.summary(),
            "host": {"start": host_start, "end": _host_info()},
        }
        if lane["fallbacks"]:
            # the guard served this merge via the LOCAL cpu path: the
            # wall number is not an offload measurement
            _abort(f"offload lane fell back to local cpu "
                  f"({lane['last_fallback']})", detail=detail)
        if remote_digest != local_digest:
            _abort("offloaded output diverges from local compaction "
                  f"(local {local_digest} vs remote {remote_digest})",
                  detail=detail)
        detail["byte_equal"] = True
        _emit({"metric": _offload_metric_name(),
               "value": round(offload_s / local_s, 3), "unit": "x",
               "vs_baseline": None, "detail": detail})
    finally:
        if svc is not None:
            svc.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    _arm_watchdog()
    n_total, n_runs, value_size, reps = _bench_params()

    # 1) fill + pack + CPU lane, all in-process, all pure numpy — the
    # parent never imports jax (the lane child must be able to hold the chip)
    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.ops.compact import CpuBackend, pack_runs

    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    host_start = _host_info()
    runs, fill_s = _fill(n_total, n_runs, value_size)
    opts, fargs = _compact_opts()
    # the session turns the instrumented pipeline spans (pack / device /
    # gather) into the per-stage `trace` breakdown of the JSON detail —
    # summed over all reps (see `calls`), present in failure diagnostics too
    proc_t0 = time.process_time()
    with COMPACT_TRACER.session() as cpu_sess:
        packed = pack_runs(runs, opts, need_sbytes=True)
        concat = KVBlock.concat(runs)
        n_in = sum(packed.lens)
        cpu_s, cpu_out, cpu_split = _lane(CpuBackend(), packed, concat,
                                          fargs, reps)
    cpu_process_s = time.process_time() - proc_t0
    cpu_digest = _out_digest(cpu_out)
    cpu_detail = {
        "fill_s": round(fill_s, 3),
        "cpu_compact_s": round(cpu_s, 3),
        "cpu_split": cpu_split,
        "cpu_records_per_s": int(n_in / cpu_s),
        # process cpu-seconds across pack+lane vs their wall time: the
        # contention tell for an unexplained cpu-lane regression
        "cpu_process_s": round(cpu_process_s, 3),
        "input_records": n_in,
        "output_records": cpu_digest["n_out"],
        "trace": cpu_sess.summary(),
        "host": {"start": host_start, "end": _host_info()},
    }

    # 2) device lane, in the one child that may hold the chip. Free the
    # parent's copies before the child builds its own: peak RSS stays
    # one-process-sized
    del runs, packed, concat, cpu_out
    lane_timeout = float(os.environ.get("PEGASUS_BENCH_LANE_S", 360))
    lane_result, reason, status = _run_tpu_lane_child(lane_timeout)

    if lane_result is None:
        # no device number exists: fail, and keep the cpu lane's numbers
        # (plus the stopped child's last heartbeat — wedged stage, lane
        # guard totals) on stderr as diagnostics, never as a result
        detail = dict(cpu_detail)
        if status:
            detail["watchdog"] = status
        _abort(f"device lane unavailable ({reason})", detail=detail)

    if (lane_result["n_out"], lane_result["key_sha"],
            lane_result["val_sha"]) != (cpu_digest["n_out"],
                                        cpu_digest["key_sha"],
                                        cpu_digest["val_sha"]):
        _abort("backend outputs diverge", detail={
            "cpu": cpu_digest,
            "device": {k: lane_result[k]
                       for k in ("n_out", "key_sha", "val_sha")}})

    tpu_s = lane_result["tpu_s"]
    speedup = cpu_s / tpu_s
    detail = dict(cpu_detail)
    detail.update({
        "tpu_compact_s": round(tpu_s, 3),
        "tpu_split": lane_result["split"],
        "tpu_records_per_s": int(n_in / tpu_s),
        "byte_equal": True,
        "device": lane_result["device"],
        # fallbacks/retries/breaker trips recorded by the child's lane
        # guard — readers must check these before trusting the speedup
        # as a true device number
        "lane": lane_result.get("lane"),
    })
    _emit({
        "metric": _metric_name(n_total, n_runs, value_size,
                               lane_result["device"]["platform"]),
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "detail": detail,
    })


_MODES = {"ycsb": ycsb_main, "learn": learn_main, "offload": offload_main,
          "native": native_main}

if __name__ == "__main__":
    if "--tpu-lane" in sys.argv:
        tpu_lane_main()
        sys.exit(0)
    _mode = os.environ.get("PEGASUS_BENCH_MODE", "")
    try:
        if _mode in _MODES:
            _arm_watchdog()
            _MODES[_mode]()
        else:
            main()
    except Exception as e:  # noqa: BLE001 - boundary: name the failure, exit non-zero
        import traceback

        traceback.print_exc()
        _abort(f"bench crashed: {e!r}")
