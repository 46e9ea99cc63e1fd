"""Chaos suite for the compaction lane guard (runtime/lane_guard.py).

Every fail point threaded through the pipeline is driven here with the
sleep()/raise() verbs: an injected device hang must be abandoned at the
deadline and fall back to the cpu backend with BYTE-EQUAL output; injected
transient errors must retry, then fall back; N consecutive failures must
open the circuit breaker, which re-probes via the watchdog before closing.
Everything is seeded-RNG deterministic and runs in tier-1 (not slow).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from pegasus_tpu.base import consts
from pegasus_tpu.ops.compact import CompactOptions, compact_blocks
from pegasus_tpu.runtime import fail_points as fp
from pegasus_tpu.runtime.lane_guard import (LANE_GUARD, LaneDeadlineExceeded,
                                            LaneGuardConfig)
from pegasus_tpu.runtime.perf_counters import counters
from tests.test_compact_ops import _adversarial_records, make_block

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def guard():
    """Deterministic small-knob config; fail points armed; everything
    restored afterwards (LANE_GUARD is process-wide)."""
    saved_cfg, saved_probe = LANE_GUARD.config, LANE_GUARD.probe_fn
    LANE_GUARD.config = LaneGuardConfig(
        deadline_s=60.0, max_retries=1, backoff_base_s=0.001,
        backoff_max_s=0.002, breaker_threshold=2, breaker_cooldown_s=60.0,
        compile_wait_s=600.0)
    LANE_GUARD.probe_fn = lambda: True
    LANE_GUARD.reset()
    fp.setup()
    yield LANE_GUARD
    fp.teardown()
    LANE_GUARD.config, LANE_GUARD.probe_fn = saved_cfg, saved_probe
    LANE_GUARD.reset()


def _runs(seed=3, n=220, k=2):
    rng = np.random.default_rng(seed)
    return [make_block(_adversarial_records(rng, n)) for _ in range(k)]


def _assert_byte_equal(a, b):
    assert a.n == b.n
    np.testing.assert_array_equal(a.key_arena, b.key_arena)
    np.testing.assert_array_equal(a.val_arena, b.val_arena)
    np.testing.assert_array_equal(a.expire_ts, b.expire_ts)
    np.testing.assert_array_equal(a.deleted, b.deleted)


# ------------------------------------------- a guarded call never compiles


class _SlowJit:
    """A jitted fn whose lowering takes `delay` seconds (a cold XLA:TPU
    merge compile takes minutes) or raises `error`."""

    def __init__(self, jitted, delay=0.0, error=None):
        self._jitted, self._delay, self._error = jitted, delay, error
        self.lowered = 0

    def lower(self, *args):
        import time

        self.lowered += 1
        time.sleep(self._delay)
        if self._error is not None:
            raise self._error
        return self._jitted.lower(*args)


def _slow_kernel(delay=0.0, error=None):
    from pegasus_tpu.ops.kernel import DeviceKernel

    k = DeviceKernel(lambda x: x + 1, "test_slow")
    k._jit = _SlowJit(k._jit, delay, error)
    return k


def _production_wait(guard):
    """The production setting: a call with a fallback never waits."""
    from dataclasses import replace

    guard.config = replace(guard.config, compile_wait_s=0.0)


def test_cold_kernel_is_served_by_the_host_lane_and_counted(guard):
    """COMPILE-BEHIND: the first guarded call of a shape does not compile
    under the deadline (nor wait for the compiler): the compile runs on
    the compile pool, the fallback serves the call, only `compile_behind`
    moves — no fallback/retry/failure total, the breaker untouched — and
    the next call of that shape runs the compiled program."""
    import time

    _production_wait(guard)
    kernel = _slow_kernel(delay=0.5)
    x = np.arange(4, dtype=np.int32)
    t0 = time.monotonic()
    out = guard.run(lambda: np.asarray(kernel(x)), lambda: "cpu", op="t",
                    deadline_s=0.2)
    assert out == "cpu" and time.monotonic() - t0 < 0.4
    st = guard.state()
    assert st["compile_behind"] == 1 and st["compile_wait_timeouts"] == 0
    assert st["fallbacks"] == st["retries"] == st["device_failures"] == 0
    assert st["deadline_abandons"] == 0 and not st["breaker_open"]
    # the compile pool finishes the program (0.5 s of lowering, seconds
    # when the whole suite loads the box)
    from pegasus_tpu.ops.kernel import compile_report

    give_up = time.monotonic() + 20
    while compile_report()["inflight"] and time.monotonic() < give_up:
        time.sleep(0.05)
    out = guard.run(lambda: np.asarray(kernel(x)), lambda: "cpu", op="t",
                    deadline_s=0.2)
    np.testing.assert_array_equal(out, x + 1)
    assert guard.state()["compile_behind"] == 1


def test_compile_wait_is_outside_the_deadline_and_bounded(guard):
    """A caller that asked for the device (`with compile_wait():`, or no
    fallback) waits for the compile on its own thread: a compile longer
    than the deadline abandons nothing, and the device part is still
    bounded by the deadline. A wait that runs out is counted."""
    import time

    from pegasus_tpu.runtime.lane_guard import LaneError, compile_wait

    _production_wait(guard)
    x = np.arange(4, dtype=np.int32)
    kernel = _slow_kernel(delay=0.5)
    with compile_wait():
        out = guard.run(lambda: np.asarray(kernel(x)), lambda: "cpu",
                        op="t", deadline_s=0.2)
    np.testing.assert_array_equal(out, x + 1)
    kernel = _slow_kernel(delay=0.5)
    out = guard.run(lambda: np.asarray(kernel(x)), None, op="t",
                    deadline_s=0.2)
    np.testing.assert_array_equal(out, x + 1)
    st = guard.state()
    assert st["compile_behind"] == st["deadline_abandons"] == 0
    assert st["device_failures"] == st["retries"] == 0

    def wedges_after_compile():
        kernel(x)
        time.sleep(5)

    with compile_wait():
        assert guard.run(wedges_after_compile, lambda: "cpu", op="t",
                         deadline_s=0.2) == "cpu"
    assert guard.state()["deadline_abandons"] == 1
    guard.reset()

    kernel = _slow_kernel(delay=0.6)
    with compile_wait(0.1):
        assert guard.run(lambda: kernel(x), lambda: "cpu", op="t") == "cpu"
        with pytest.raises(LaneError, match="still compiling"):
            guard.run(lambda: kernel(x), None, op="t")
    st = guard.state()
    assert st["compile_behind"] == st["compile_wait_timeouts"] == 2
    assert st["fallbacks"] == st["device_failures"] == 0


def test_compiler_error_is_an_ordinary_device_failure(guard):
    """A program the compiler refuses fails every call of that shape
    through the normal policy: failure totals, retry, fallback."""
    kernel = _slow_kernel(error=RuntimeError("Mosaic refused"))
    x = np.arange(4, dtype=np.int32)
    assert guard.run(lambda: kernel(x), lambda: "cpu", op="t") == "cpu"
    st = guard.state()
    assert st["fallbacks"] == 1 and st["retries"] == 1
    assert st["device_failures"] == 2 and st["compile_behind"] == 0
    assert "Mosaic refused" in st["last_failure"]["error"]


def test_nested_guard_leaves_the_compile_decision_to_the_outermost(guard):
    _production_wait(guard)
    kernel = _slow_kernel(delay=0.3)
    x = np.arange(4, dtype=np.int32)

    def outer_device():
        return guard.run(lambda: np.asarray(kernel(x)),
                         lambda: "inner-cpu", op="inner")

    assert guard.run(outer_device, lambda: "outer-cpu", op="outer") \
        == "outer-cpu"
    assert guard.state()["compile_behind"] == 1


def test_unguarded_callers_compile_once_on_their_own_thread():
    """Outside a guard (a residency prime, a bench, a direct backend call)
    the caller compiles, and concurrent callers of one program wait for
    that one compile."""
    import threading

    from pegasus_tpu.ops.kernel import compile_report

    kernel = _slow_kernel(delay=0.3)
    x = np.arange(4, dtype=np.int32)
    outs = []
    threads = [threading.Thread(target=lambda: outs.append(
        np.asarray(kernel(x)))) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(outs) == 4 and all((o == x + 1).all() for o in outs)
    assert kernel._jit.lowered == 1
    # a different input signature is a different program
    np.testing.assert_array_equal(
        np.asarray(kernel(np.arange(8, dtype=np.int32))),
        np.arange(8) + 1)
    assert kernel._jit.lowered == 2
    report = compile_report()
    assert report["compiled"] >= 2 and report["max_s"] >= 0.3


# ------------------------------------------------------- fail-point verbs


def test_sleep_and_raise_verbs():
    import time

    fp.setup()
    try:
        fp.cfg("chaos.sleep", "sleep(40)")
        t0 = time.perf_counter()
        assert fp.fail_point("chaos.sleep") is None  # sleeps, then continues
        assert time.perf_counter() - t0 >= 0.035
        fp.cfg("chaos.raise", "raise(boom)")
        with pytest.raises(fp.FailPointError, match="boom"):
            fp.fail_point("chaos.raise")
        # count modifier applies to the new verbs too
        fp.cfg("chaos.once", "1*raise(once)")
        with pytest.raises(fp.FailPointError):
            fp.fail_point("chaos.once")
        assert fp.fail_point("chaos.once") is None
    finally:
        fp.teardown()


# --------------------------------------------------- deadline + fallback


def test_injected_hang_deadline_abandons_and_falls_back(guard):
    """Acceptance: a fail-point-injected device hang completes via cpu
    fallback within deadline + backoff (no external kill), byte-identical
    to a clean cpu compaction, and the incident is visible in /metrics."""
    guard.config.deadline_s = 0.25
    runs = _runs(seed=5)
    opts = dict(now=100, bottommost=True)
    want = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    fp.cfg("compact.device", "1*sleep(1500)")
    got = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    _assert_byte_equal(want.block, got.block)
    st = guard.state()
    assert st["deadline_abandons"] == 1
    assert st["fallbacks"] == 1
    assert st["retries"] == 0  # a wedge must NOT retry
    assert "device" in st["last_failure"]["error"]  # stage attribution
    # the incident is scrape-visible on /metrics
    from pegasus_tpu.collector.reporter import prometheus_text

    text = prometheus_text()
    assert "compact_lane_fallback_count" in text
    assert "compact_lane_deadline_abandon_count" in text


def test_transient_raise_retries_then_succeeds(guard):
    """One transient device error: bounded retry recovers ON DEVICE (no
    fallback), and the breaker's consecutive count resets."""
    runs = _runs(seed=7)
    opts = dict(now=100, bottommost=True)
    want = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    fp.cfg("compact.device", "1*raise(transient h2d glitch)")
    got = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    _assert_byte_equal(want.block, got.block)
    st = guard.state()
    assert st["retries"] == 1
    assert st["fallbacks"] == 0
    assert st["breaker_consecutive_failures"] == 0  # success reset it


def test_raise_exhausts_retries_then_falls_back(guard):
    guard.config.breaker_threshold = 99  # isolate the retry/fallback path
    runs = _runs(seed=9)
    opts = dict(now=100, bottommost=True)
    want = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    fp.cfg("compact.device", "raise(device dead)")
    got = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    _assert_byte_equal(want.block, got.block)
    st = guard.state()
    assert st["retries"] == 1  # max_retries=1 -> two attempts
    assert st["fallbacks"] == 1
    assert st["device_failures"] == 2


@pytest.mark.parametrize("point", ["compact.pack", "compact.h2d",
                                   "compact.gather"])
def test_every_stage_fail_point_falls_back_byte_equal(guard, point):
    """Chaos at every instrumented stage boundary: the guard's fallback
    contract holds no matter WHERE the device lane dies. Count-limited
    arming (2*) means both device attempts die and the cpu rerun is clean
    even for stages shared with the cpu lane (pack)."""
    runs = _runs(seed=11)
    opts = dict(now=100, bottommost=True)
    want = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    fp.cfg(point, "2*raise(chaos)")
    got = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    _assert_byte_equal(want.block, got.block)
    assert guard.state()["fallbacks"] == 1


# ------------------------------------------------------- circuit breaker


def test_breaker_opens_cooldown_and_reprobes_before_closing(guard):
    probes = []

    def probe():
        probes.append(1)
        return probe_result[0]

    probe_result = [False]
    guard.probe_fn = probe
    runs = _runs(seed=13)
    opts = dict(now=100, bottommost=True)
    fp.cfg("compact.device", "raise(hard down)")
    # one guarded compaction = 2 attempts = 2 consecutive failures ->
    # threshold 2 trips the breaker
    compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    st = guard.state()
    assert st["breaker_open"] and st["breaker_trips"] == 1
    assert counters.number("compact.lane.breaker_open").value() == 1
    # cooldown active: routed straight to cpu, device NOT attempted
    failures_before = st["device_failures"]
    got = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    assert guard.state()["device_failures"] == failures_before
    assert guard.state()["fallbacks"] == 2
    assert not probes  # no re-probe while the cooldown is running
    want = compact_blocks(runs, CompactOptions(backend="cpu", **opts))
    _assert_byte_equal(want.block, got.block)
    # cooldown lapses -> half-open: a FAILING probe keeps it open
    guard._breaker_open_until = 0.0
    assert guard.breaker_open() is True
    assert len(probes) == 1
    assert guard.state()["breaker_cooldown_remaining_s"] > 0  # re-armed
    # a PASSING probe closes it and the device lane runs again
    guard._breaker_open_until = 0.0
    probe_result[0] = True
    assert guard.breaker_open() is False
    assert counters.number("compact.lane.breaker_open").value() == 0
    fp.cfg("compact.device", "off()")
    got2 = compact_blocks(runs, CompactOptions(backend="tpu", **opts))
    _assert_byte_equal(want.block, got2.block)
    assert guard.state()["breaker_consecutive_failures"] == 0


def test_nested_fallback_does_not_reset_breaker(guard):
    """A device_fn that 'succeeds' only because a NESTED guarded call fell
    back to cpu (sharded reassembly sorts re-enter compact_blocks) must
    not be credited as device health — the breaker still accumulates."""
    guard.config.breaker_threshold = 3

    def device_with_nested_degrade():
        guard.record_device_failure("nested", "inner lane died")
        return "ok"

    for _ in range(3):
        assert guard.run(device_with_nested_degrade, lambda: "cpu") == "ok"
    st = guard.state()
    assert st["breaker_open"] and st["breaker_trips"] == 1


def test_passive_breaker_check_never_probes(guard):
    """breaker_open(probe=False) — the engine write path's check — must
    stay open without running a half-open device probe, even after the
    cooldown lapsed; only a probing caller may close the breaker."""
    probes = []
    guard.probe_fn = lambda: probes.append(1) or True
    guard.record_device_failure("compact", "down")
    guard.record_device_failure("compact", "down")  # threshold 2: open
    guard._breaker_open_until = 0.0  # cooldown already lapsed
    assert guard.breaker_open(probe=False) is True
    assert not probes
    assert guard.breaker_open() is False  # the probing caller closes it
    assert len(probes) == 1


def test_capacity_local_failures_do_not_advance_breaker(guard):
    """Per-sst HBM prime OOMs are capacity-local, not device death: they
    are recorded but must never flap the breaker open."""
    for _ in range(5):
        guard.record_device_failure("device_run_prime", "RESOURCE_EXHAUSTED",
                                    breaker=False)
    st = guard.state()
    assert not st["breaker_open"]
    assert st["breaker_consecutive_failures"] == 0
    assert st["device_failures"] == 5


# --------------------------------------------------- pipelined blockwise


def _blockwise_runs(seed=5, n=400, k=2):
    from pegasus_tpu.ops.compact import sort_block

    rng = np.random.default_rng(seed)
    return [sort_block(make_block(_adversarial_records(rng, n)),
                       CompactOptions(backend="cpu")) for _ in range(k)]


def test_wedged_pipeline_prefetch_abandoned_cpu_rerun_byte_equal(guard):
    """Satellite (ISSUE 4): a wedged PREFETCH worker (armed at the
    compact.pipeline stage) stalls the pipelined blockwise lane; the lane
    guard's deadline abandons it WITHOUT deadlocking the drain — the
    serial cpu rerun completes promptly and byte-identical."""
    import time

    guard.config.deadline_s = 0.3
    runs = _blockwise_runs()
    base = dict(now=100, bottommost=True, runs_sorted=True)
    want = compact_blocks(runs, CompactOptions(backend="cpu", **base))
    fp.cfg("compact.pipeline", "sleep(1500)")
    t0 = time.perf_counter()
    got = compact_blocks(runs, CompactOptions(
        backend="tpu", max_device_records=200, **base))
    elapsed = time.perf_counter() - t0
    _assert_byte_equal(want.block, got.block)
    st = guard.state()
    assert st["deadline_abandons"] == 1
    assert st["fallbacks"] == 1
    assert st["retries"] == 0  # a wedge must NOT retry
    # the cpu rerun did not wait out the 1.5s wedge: abandon + rerun
    # only (waiting it out would be >= 1.5 + rerun; the 0.9s scaled
    # deadline + rerun can brush 1.3 on a loaded 1-core box)
    assert elapsed < 1.45, elapsed
    # the stall was attributable (open pipeline.stall span in the
    # abandoned lane thread)
    assert "pipeline.stall" in st["last_failure"]["error"]


def test_pipeline_device_raise_drains_then_falls_back_byte_equal(guard):
    """A raising device stage inside the pipelined blockwise lane drains
    the in-flight prefetch workers (no deadlock), retries, then falls
    back to the serial cpu rerun byte-identically."""
    runs = _blockwise_runs(seed=21)
    base = dict(now=100, bottommost=True, runs_sorted=True)
    want = compact_blocks(runs, CompactOptions(backend="cpu", **base))
    fp.cfg("compact.device", "raise(pipelined lane down)")
    drains_before = counters.rate("compact.pipeline.drain_count")._value
    got = compact_blocks(runs, CompactOptions(
        backend="tpu", max_device_records=200, **base))
    _assert_byte_equal(want.block, got.block)
    st = guard.state()
    assert st["fallbacks"] == 1
    assert st["retries"] == 1  # transient-looking: the guard retried
    # both guarded attempts drained the pipeline before giving it back
    drained = counters.rate("compact.pipeline.drain_count")._value \
        - drains_before
    assert drained == 2, drained


# ------------------------------------------- batched + sharded call sites


def test_batched_wedged_prefetch_restacks_inline_no_hang(guard):
    """A wedged stacking prefetch in the batched path (which runs OUTSIDE
    any lane guard) must not hang compact_partition_batch: the bounded
    prefetch pickup abandons the worker at the lane deadline and the
    chunk re-stacks inline under its own guard, byte-equal."""
    import time

    from dataclasses import replace

    from pegasus_tpu.ops.batched_compact import compact_partition_batch
    from tests.test_batched_compact import make_partition

    guard.config.deadline_s = 0.3
    # max_device_records below 2x the per-job padded rows forces ONE job
    # per chunk -> 2 chunks -> the map actually pipelines (n > 1) and the
    # prefetch really rides a pool worker where compact.pipeline fires
    opts = CompactOptions(backend="tpu", now=60, bottommost=True,
                          runs_sorted=True, max_device_records=600)
    jobs = []
    for pidx in range(2):
        runs, drs = make_partition(70 + pidx, 250)
        assert sum(d.padded_len for d in drs) <= 600
        jobs.append((runs, drs, pidx))
    # compile the batched kernel first: the timing below is about the
    # wedge, and a cold compile is waited for OUTSIDE the deadline
    compact_partition_batch(jobs, opts)
    fp.cfg("compact.pipeline", "sleep(2000)")
    t0 = time.perf_counter()
    outs = compact_partition_batch(jobs, opts)
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.8, elapsed  # bounded by the deadline, not the wedge
    fp.cfg("compact.pipeline", "off()")
    for (runs, _, pidx), got in zip(jobs, outs):
        want = compact_blocks(runs, replace(opts, pidx=pidx, backend="cpu"))
        _assert_byte_equal(want.block, got)


def test_batched_compact_falls_back_byte_equal(guard):
    from dataclasses import replace

    from pegasus_tpu.ops.batched_compact import compact_partition_batch
    from tests.test_batched_compact import make_partition

    opts = CompactOptions(backend="tpu", now=60, bottommost=True,
                          runs_sorted=True)
    jobs = []
    for pidx in range(3):
        runs, drs = make_partition(50 + pidx, 300)
        jobs.append((runs, drs, pidx))
    fp.cfg("compact.device", "raise(vmap lane down)")
    outs = compact_partition_batch(jobs, opts)
    assert guard.state()["fallbacks"] >= 1
    fp.cfg("compact.device", "off()")
    for (runs, _, pidx), got in zip(jobs, outs):
        want = compact_blocks(runs, replace(opts, pidx=pidx, backend="cpu"))
        _assert_byte_equal(want.block, got)


def test_sharded_compact_block_falls_back_byte_equal(guard):
    from dataclasses import replace

    from pegasus_tpu.parallel import make_mesh, sharded_compact_block

    mesh = make_mesh(8)
    rng = np.random.default_rng(17)
    blocks = [make_block(_adversarial_records(rng, 250)) for _ in range(2)]
    opts = CompactOptions(backend="tpu", now=100, bottommost=True)
    fp.cfg("compact.device", "raise(collective wedged)")
    got = sharded_compact_block(blocks, mesh, opts)
    assert guard.state()["fallbacks"] >= 1
    fp.cfg("compact.device", "off()")
    want = compact_blocks(blocks, replace(opts, backend="cpu"))
    _assert_byte_equal(want.block, got.block)


# --------------------------------------------------- engine/service level


@pytest.fixture
def srv(tmp_path):
    from pegasus_tpu.engine import EngineOptions
    from pegasus_tpu.engine.server_impl import PegasusServer

    s = PegasusServer(str(tmp_path / "db"),
                      options=EngineOptions(backend="tpu"))
    yield s
    s.close()


def _fill(srv, n=40):
    from pegasus_tpu.base import key_schema

    for i in range(n):
        srv.engine.put(key_schema.generate_key(b"h", b"s%03d" % i),
                       b"\x82" + b"\0" * 12 + b"v%d" % i)


def test_manual_compact_survives_device_hang_and_reports(guard, srv):
    """Acceptance end-to-end: a device hang during manual compaction is
    abandoned at the deadline, the compaction completes via cpu fallback,
    and the incident is visible in query_compact_state, device-health,
    and /metrics."""
    from pegasus_tpu.engine.manual_compact_service import ManualCompactService
    from pegasus_tpu.ops.device_watchdog import WATCHDOG

    guard.config.deadline_s = 0.25
    guard.config.breaker_threshold = 99
    _fill(srv)
    svc = ManualCompactService(srv, mock_now=1000)
    fp.cfg("compact.device", "sleep(1200)")
    assert svc.start_manual_compact_if_needed(
        {consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "900"})
    # the data survived, served identically
    from pegasus_tpu.base import key_schema

    assert srv.engine.get(key_schema.generate_key(b"h", b"s000"),
                          now=50) is not None
    fp.cfg("compact.device", "off()")
    state = svc.query_compact_state()
    assert "idle; last finish" in state
    assert "cpu fallbacks:" in state
    assert guard.state()["deadline_abandons"] >= 1
    # device-health surfaces the lane guard state
    health = WATCHDOG.state()
    assert health["lane"]["fallbacks"] >= 1
    # the trace session survived the guard's worker-thread hop: the run
    # still records a per-stage breakdown
    assert svc.last_trace and "sst_write" in svc.last_trace


def test_failed_manual_compact_is_not_deduped_as_finished(guard, tmp_path):
    """Satellite: a raising compaction must NOT persist finish state (the
    once-trigger would be deduped as 'finished' and never retried); the
    failure surfaces in query_compact_state, and re-delivering the same
    trigger retries."""
    from pegasus_tpu.engine import EngineOptions
    from pegasus_tpu.engine.manual_compact_service import ManualCompactService
    from pegasus_tpu.engine.server_impl import PegasusServer

    s = PegasusServer(str(tmp_path / "db"),
                      options=EngineOptions(backend="cpu"))
    try:
        _fill(s)
        svc = ManualCompactService(s, mock_now=1000)
        envs = {consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "900"}
        fp.cfg("engine.sst_write", "1*raise(injected disk failure)")
        with pytest.raises(fp.FailPointError):
            svc.start_manual_compact_if_needed(envs)
        # finish state NOT recorded
        assert "pegasus_last_manual_compact_finish_time" \
            not in s.engine.meta_store
        assert svc.last_finish_time_ms == 0
        state = svc.query_compact_state()
        assert "FAILED" in state and "disk failure" in state
        # the SAME trigger retries now that the fault cleared
        svc.set_mock_now(1100)
        assert svc.start_manual_compact_if_needed(envs)
        assert s.engine.meta_store[
            "pegasus_last_manual_compact_finish_time"] == 1100
        assert "FAILED" not in svc.query_compact_state()
    finally:
        s.close()


# ------------------------------------------------------- the read lane


@pytest.fixture
def read_guard():
    from pegasus_tpu.runtime.lane_guard import READ_LANE_GUARD

    saved = READ_LANE_GUARD.config
    READ_LANE_GUARD.config = LaneGuardConfig(
        deadline_s=30.0, max_retries=1, backoff_base_s=0.001,
        backoff_max_s=0.002, breaker_threshold=2, breaker_cooldown_s=60.0,
        compile_wait_s=600.0)
    READ_LANE_GUARD.probe_fn = lambda: True
    READ_LANE_GUARD.reset()
    fp.setup()
    yield READ_LANE_GUARD
    fp.teardown()
    READ_LANE_GUARD.config = saved
    READ_LANE_GUARD.probe_fn = None
    READ_LANE_GUARD.reset()


def _read_engine(tmp_path):
    from pegasus_tpu.base import key_schema
    from pegasus_tpu.engine.db import EngineOptions, LsmEngine

    eng = LsmEngine(str(tmp_path / "rdb"), EngineOptions(
        backend="tpu", device_reads=True, device_read_min_batch=1,
        l0_compaction_trigger=100))
    for i in range(30):
        eng.put(key_schema.generate_key(b"h", b"s%03d" % i),
                b"\x82" + b"\0" * 12 + b"v%d" % i)
    eng.flush()
    with eng._lock:
        ssts = eng._all_ssts_locked()
    for s in ssts:
        eng._device_run_budgeted(s)
    keys = [key_schema.generate_key(b"h", b"s%03d" % i) for i in range(32)]
    return eng, keys


def test_wedged_device_read_abandons_and_serves_host_byte_equal(
        guard, read_guard, tmp_path):
    """Satellite chaos: a wedged device read is deadline-abandoned and
    the host fallback serves the identical answers — within the read
    deadline, not the wedge's duration."""
    import time

    read_guard.config.deadline_s = 0.25
    eng, keys = _read_engine(tmp_path)
    try:
        want = [eng.get(k, now=100) for k in keys]
        fp.cfg("read.device", "1*sleep(1500)")
        t0 = time.perf_counter()
        got = eng.get_batch(keys, now=100)
        elapsed = time.perf_counter() - t0
        assert got == want
        st = read_guard.state()
        assert st["deadline_abandons"] == 1
        assert st["fallbacks"] == 1
        assert st["retries"] == 0  # a wedge must NOT retry
        assert elapsed < 1.2, elapsed
    finally:
        eng.close()


def test_read_breaker_trips_without_opening_compact_lane(
        guard, read_guard, tmp_path):
    """Satellite: the read lane's breaker is ITS OWN — tripping it routes
    reads to the host walk while the compact lane stays closed and
    device compaction keeps running (and its counters stay untouched)."""
    eng, keys = _read_engine(tmp_path)
    try:
        fp.cfg("read.device", "raise(probe hard down)")
        # one guarded read batch = 2 attempts = threshold 2: breaker trips
        want = [eng.get(k, now=100) for k in keys]
        assert eng.get_batch(keys, now=100) == want
        st = read_guard.state()
        assert st["breaker_open"] and st["breaker_trips"] == 1
        assert counters.number("read.lane.breaker_open").value() == 1
        # breaker open: reads route straight to host, device NOT probed
        failures = st["device_failures"]
        assert eng.get_batch(keys, now=100) == want
        assert read_guard.state()["device_failures"] == failures
        # the COMPACT lane is untouched: breaker closed, no fallbacks,
        # and a device compaction still runs clean
        cst = guard.state()
        assert not cst["breaker_open"]
        assert cst["fallbacks"] == 0 and cst["device_failures"] == 0
        runs = _runs(seed=23)
        got = compact_blocks(runs, CompactOptions(
            backend="tpu", now=100, bottommost=True))
        want_c = compact_blocks(runs, CompactOptions(
            backend="cpu", now=100, bottommost=True))
        _assert_byte_equal(want_c.block, got.block)
        assert guard.state()["fallbacks"] == 0
    finally:
        eng.close()


def test_compact_breaker_does_not_block_device_reads(
        guard, read_guard, tmp_path):
    """The mirror isolation: a tripped COMPACT breaker must not push
    reads off already-resident runs (the read lane judges the device
    independently). Primes ride the compact lane's breaker, so residency
    is established BEFORE the trip — exactly the production shape: the
    data is on the chip, compactions degrade, reads keep serving."""
    eng, keys = _read_engine(tmp_path)
    guard.record_device_failure("compact", "down")
    guard.record_device_failure("compact", "down")  # threshold 2: open
    assert guard.state()["breaker_open"]
    try:
        before = counters.number("read.device.lookup_count").value()
        want = [eng.get(k, now=100) for k in keys]
        assert eng.get_batch(keys, now=100) == want
        assert counters.number("read.device.lookup_count").value() > before
        assert read_guard.state()["fallbacks"] == 0
    finally:
        eng.close()


# ------------------------------------------------------------- CI wiring


def test_fail_point_lint_clean():
    """tools/check_fail_points.py wired into the test run: every
    test-armed fail point exists in source, every source point is
    documented in README."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_fail_points.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
