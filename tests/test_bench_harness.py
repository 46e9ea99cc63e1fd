"""bench.py harness bounds: the driver artifact is (rc, stdout), and a
measurement path that cannot produce its number must FAIL — non-zero exit,
the reason on stderr, and no result line on stdout that a reader could
take for a device number. These tests run bench.py exactly as a driver
does — a subprocess under a wall-clock bound — through every failure mode
the device lane has produced:

  - lane child hangs after a healthy start -> PEGASUS_BENCH_FAKE_LANE=sleep
  - lane child dies in backend init -> FAKE_LANE=crash
  - everything hangs and only the watchdog is left -> tiny TIMEOUT_S

The happy path (real child lane on the CPU platform) is covered too, so
the digest-equality handshake between parent and child stays exercised.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _json_lines(text):
    return [json.loads(l) for l in text.strip().splitlines()
            if l.startswith("{")]


def run_bench(env_extra, timeout_s, n=30_000):
    """-> (rc, stdout JSON lines, stderr text, elapsed)."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PEGASUS_BENCH_N": str(n),
        "PEGASUS_BENCH_REPS": "1",
    })
    env.update(env_extra)
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, BENCH], capture_output=True,
                          text=True, timeout=timeout_s, env=env, cwd=REPO)
    elapsed = time.monotonic() - t0
    return proc.returncode, _json_lines(proc.stdout), proc.stderr, elapsed


def _assert_failed_without_a_number(rc, lines, stderr):
    """The failure contract: non-zero exit, the reason named on stderr,
    and NOTHING on stdout — in particular no cpu timing under the tpu
    metric's name."""
    assert rc != 0
    assert lines == [], f"a failed bench printed a result line: {lines}"
    assert "bench FAILED" in stderr


def test_lane_wedge_after_start_bounded():
    """The device lane wedges after a healthy start. The parent must stop
    the child and FAIL within the lane budget + slack; the cpu lane's
    numbers survive as stderr diagnostics only."""
    rc, lines, stderr, elapsed = run_bench(
        {"PEGASUS_BENCH_FAKE_LANE": "sleep", "PEGASUS_BENCH_LANE_S": "4"},
        timeout_s=120)
    _assert_failed_without_a_number(rc, lines, stderr)
    assert "exceeded 4s" in stderr
    diag = _json_lines(stderr)[-1]
    assert diag["cpu_compact_s"] > 0
    assert diag["input_records"] == 30_000
    assert "metric" not in diag  # diagnostics, not a result under a name
    assert elapsed < 90


def test_lane_crash_fails_with_the_childs_reason():
    rc, lines, stderr, _ = run_bench({"PEGASUS_BENCH_FAKE_LANE": "crash"},
                                     timeout_s=120)
    _assert_failed_without_a_number(rc, lines, stderr)
    assert "rc=7" in stderr and "boom" in stderr


def test_watchdog_backstop_fails_the_run():
    """If everything else stalls, the watchdog itself must end the run:
    non-zero exit, the reason named, no result line."""
    env = {"PEGASUS_BENCH_FAKE_LANE": "sleep", "PEGASUS_BENCH_LANE_S": "3600",
           "PEGASUS_BENCH_TIMEOUT_S": "8"}
    rc, lines, stderr, elapsed = run_bench(env, timeout_s=120)
    _assert_failed_without_a_number(rc, lines, stderr)
    assert "watchdog fired" in stderr
    assert elapsed < 60


def test_no_tpu_and_no_explicit_platform_refused():
    """JAX_PLATFORMS unset on a host with no TPU: jax would quietly pick
    the cpu. The device lane must refuse, naming the platform it found."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.update({"PEGASUS_BENCH_N": "6000", "PEGASUS_BENCH_REPS": "1"})
    proc = subprocess.run([sys.executable, BENCH], capture_output=True,
                          text=True, timeout=300, env=env, cwd=REPO)
    _assert_failed_without_a_number(proc.returncode,
                                    _json_lines(proc.stdout), proc.stderr)
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


@pytest.mark.slow
def test_happy_path_child_lane_byte_equal():
    """Real child lane on the CPU platform: digest handshake across the
    process boundary, speedup value present (its magnitude is meaningless
    on CPU jax — only byte_equal and shape of the line matter here), and
    the metric's NAME says it was a cpu rehearsal."""
    rc, lines, _, _ = run_bench({}, timeout_s=600, n=6_000)
    assert rc == 0
    line = lines[-1]
    assert line["value"] is not None
    assert line["detail"]["byte_equal"] is True
    assert line["unit"] == "x"
    assert "platform cpu" in line["metric"]
    assert "tpu-backend" not in line["metric"]
    assert line["detail"]["device"]["platform"] == "cpu"


SCALE = os.path.join(REPO, "tools", "scale_bench.py")


def run_scale(env_extra, timeout_s, n=50_000, maxdev=8192):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PEGASUS_SCALE_N": str(n),
        "PEGASUS_SCALE_MAXDEV": str(maxdev),
    })
    env.update(env_extra)
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, SCALE], capture_output=True,
                          text=True, timeout=timeout_s, env=env, cwd=REPO)
    elapsed = time.monotonic() - t0
    return proc.returncode, _json_lines(proc.stdout), proc.stderr, elapsed


def test_scale_bench_wedge_bounded():
    """tools/scale_bench.py under a wedged device lane must FAIL within
    its watchdog budget: non-zero exit, no result line, the cpu lane's
    progress on stderr as diagnostics."""
    rc, lines, stderr, elapsed = run_scale({"PEGASUS_SCALE_FAKE": "sleep",
                                            "PEGASUS_SCALE_TIMEOUT_S": "12"},
                                           timeout_s=120)
    assert rc != 0
    assert lines == []
    assert "scale_bench FAILED: watchdog" in stderr
    assert '"cpu_compact_s"' in stderr
    assert elapsed < 60


def test_scale_bench_happy_blockwise():
    """Happy path on the CPU platform: the device lane takes the blockwise
    range-decomposition (n > max_device_records) and the output is
    byte-equal to the native CPU lane."""
    rc, lines, stderr, elapsed = run_scale({"PEGASUS_SCALE_TIMEOUT_S": "300"},
                                           timeout_s=360)
    assert rc == 0, stderr[-800:]
    line = lines[-1]
    assert line["detail"]["byte_equal"] is True
    assert line["detail"]["blocks"] >= 2
    assert line["value"] is not None
    assert "platform cpu" in line["metric"]


EBENCH = os.path.join(REPO, "tools", "engine_bench.py")


def test_engine_bench_wedge_bounded():
    """tools/engine_bench.py with a wedged backend init must FAIL within
    its watchdog budget — non-zero exit, no comparison line."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PEGASUS_EBENCH_N": "20000",
                "PEGASUS_EBENCH_FAKE": "sleep",
                "PEGASUS_EBENCH_TIMEOUT_S": "8"})
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, EBENCH], capture_output=True,
                          text=True, timeout=120, env=env, cwd=REPO)
    elapsed = time.monotonic() - t0
    assert proc.returncode != 0
    assert _json_lines(proc.stdout) == []
    assert "engine_bench FAILED: watchdog" in proc.stderr
    assert elapsed < 60


def test_engine_bench_happy_cpu_only():
    """Happy path: cpu-only lane completes well under the watchdog and
    prints its lane line."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PEGASUS_EBENCH_N": "20000",
                "PEGASUS_EBENCH_REPS": "1",
                "PEGASUS_EBENCH_BACKENDS": "cpu",
                "PEGASUS_EBENCH_DIR": "/tmp/pegasus_ebench_test",
                "PEGASUS_EBENCH_TIMEOUT_S": "300"})
    proc = subprocess.run([sys.executable, EBENCH], capture_output=True,
                          text=True, timeout=320, env=env, cwd=REPO)
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and lines
    lane = json.loads(lines[0])
    assert lane["backend"] == "cpu" and lane["manual_compact_s"] > 0


def test_lane_wedge_reports_stage_attribution():
    """A wedged lane whose watchdog heartbeated before dying must be
    attributed: the failure reason names the stage (no bare '360s
    exceeded'), and the stderr diagnostics carry the watchdog heartbeat
    and the cpu lane's per-stage trace."""
    rc, lines, stderr, _ = run_bench(
        {"PEGASUS_BENCH_FAKE_LANE": "wedge", "PEGASUS_BENCH_LANE_S": "4"},
        timeout_s=120)
    _assert_failed_without_a_number(rc, lines, stderr)
    assert "wedged at stage: device" in stderr
    d = _json_lines(stderr)[-1]
    assert d["watchdog"]["wedged_at_stage"] == "device"
    for stage in ("pack", "device", "gather"):
        assert stage in d["trace"], d["trace"]
    assert d["trace"]["pack"]["records"] == 30_000


def _python_procs():
    out = subprocess.run(["ps", "-eo", "args"], capture_output=True,
                         text=True).stdout.splitlines()
    return [l for l in out if "bench.py" in l or "tpu-lane" in l]


def test_ycsb_mode_smoke():
    """PEGASUS_BENCH_MODE=ycsb at tiny N: one parseable JSON line with
    ops/sec > 0, per-op-class latency percentiles, the plog group-size
    histogram + prepare-latency attribution, and a host block; the
    in-process onebox leaves no processes behind; the default mode's
    schema is untouched (covered by the other tests in this file)."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PEGASUS_BENCH_MODE": "ycsb",
        "PEGASUS_BENCH_YCSB_RECORDS": "300",
        "PEGASUS_BENCH_YCSB_OPS": "600",
        "PEGASUS_BENCH_YCSB_THREADS": "4",
        "PEGASUS_BENCH_YCSB_PARTITIONS": "4",
        "PEGASUS_BENCH_TIMEOUT_S": "150",
    })
    proc = subprocess.run([sys.executable, BENCH], capture_output=True,
                          text=True, timeout=170, env=env, cwd=REPO)
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and len(lines) == 1, \
        f"rc={proc.returncode} out={proc.stdout[-300:]} err={proc.stderr[-500:]}"
    line = json.loads(lines[0])
    assert line["unit"] == "ops/s"
    assert line["value"] and line["value"] > 0
    assert line["metric"].startswith("YCSB-A")
    d = line["detail"]
    assert d["errors"] == 0
    assert d["partitions"] == 4 and d["records"] == 300
    for cls in ("read", "update"):
        assert d["client_latency_us"][cls]["p99"] > 0
    # the batching win is attributable: group histogram + prepare latency
    assert set(d["plog"]["group_size"]) == {"p50", "p90", "p95", "p99", "p999"}
    assert d["plog"]["append_count"] > 0 and d["plog"]["flush_count"] > 0
    assert d["prepare_latency_us"]["p99"] > 0
    # host-contention attribution rides the line like the compaction bench
    assert "loadavg" in d["host"]["start"] and "cpu_count" in d["host"]["end"]
    # the self-booted onebox is in-process: nothing may outlive the bench
    assert not _python_procs(), "ycsb mode left processes behind"


def test_ycsb_read_heavy_mix_smoke():
    """PEGASUS_BENCH_YCSB_MIX=c: the read-heavy device-read A/B variant
    (ISSUE 7) — the metric names the mix, and detail.reads carries the
    device probe totals, the read-lane state, and the fallback-free
    verdict (device_numbers_degraded) so a degraded read lane can never
    pass its numbers off as clean device throughput."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PEGASUS_BENCH_MODE": "ycsb",
        "PEGASUS_BENCH_YCSB_MIX": "c",
        "PEGASUS_BENCH_YCSB_RECORDS": "200",
        "PEGASUS_BENCH_YCSB_OPS": "400",
        "PEGASUS_BENCH_YCSB_THREADS": "4",
        "PEGASUS_BENCH_YCSB_PARTITIONS": "4",
        "PEGASUS_BENCH_TIMEOUT_S": "150",
    })
    proc = subprocess.run([sys.executable, BENCH], capture_output=True,
                          text=True, timeout=170, env=env, cwd=REPO)
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and len(lines) == 1, \
        f"rc={proc.returncode} out={proc.stdout[-300:]} err={proc.stderr[-500:]}"
    line = json.loads(lines[0])
    assert line["metric"].startswith("YCSB-C 100/0")
    assert line["value"] and line["value"] > 0
    reads = line["detail"]["reads"]
    assert reads["mix"] == "c" and reads["read_fraction"] == 1.0
    assert set(reads["device"]) == {"lookup_count", "keys", "hits"}
    assert "fallbacks" in reads["lane"]
    # cpu-backend onebox: the read lane never engaged, so the device
    # numbers are clean (zero) — NOT degraded
    assert reads["device_numbers_degraded"] is False


@pytest.mark.slow
def test_ycsb_group_sweep_scaling():
    """The partition-group scaling artifact (BENCH_r06-ready): the sweep
    mode runs the same YCSB-A workload with the replica nodes split into
    1 vs 4 shared-nothing group executors. On a >=4-core host groups=4
    must clear 1.5x the ops/s of groups=1 (the single-GIL ceiling); on
    smaller hosts only the sweep mechanics are asserted — the scaling
    claim needs cores for the executors to land on."""
    cores = os.cpu_count() or 1
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PEGASUS_BENCH_MODE": "ycsb",
        "PEGASUS_BENCH_YCSB_GROUPS": "1,4",
        "PEGASUS_BENCH_YCSB_RECORDS": "2000",
        "PEGASUS_BENCH_YCSB_OPS": "16000",
        "PEGASUS_BENCH_YCSB_THREADS": "8",
        "PEGASUS_BENCH_YCSB_PARTITIONS": "8",
        "PEGASUS_BENCH_TIMEOUT_S": "560",
    })
    proc = subprocess.run([sys.executable, BENCH], capture_output=True,
                          text=True, timeout=580, env=env, cwd=REPO)
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert proc.returncode == 0 and len(lines) == 1, \
        f"rc={proc.returncode} out={proc.stdout[-300:]} err={proc.stderr[-500:]}"
    line = json.loads(lines[0])
    assert line["unit"] == "ops/s"
    assert "serve-group sweep" in line["metric"]
    sweep = line["detail"]["sweep"]
    assert [e["groups"] for e in sweep] == [1, 4]
    assert all(e["errors"] == 0 for e in sweep), sweep
    assert all(e["ops_s"] > 0 for e in sweep)
    # host-contention detail rides every sweep entry
    assert all("loadavg" in e["host"]["start"] for e in sweep)
    # no leaked group-executor processes after the bench exits
    assert not _python_procs(), "sweep left processes behind"
    if cores >= 4:
        scaling = sweep[1]["ops_s"] / sweep[0]["ops_s"]
        assert scaling >= 1.5, (
            f"groups=4 must clear 1.5x groups=1 on a {cores}-core host, "
            f"got {scaling:.2f}x ({sweep[0]['ops_s']} -> "
            f"{sweep[1]['ops_s']} ops/s)")
