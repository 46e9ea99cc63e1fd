"""Manual-compact service tests with a mocked clock, mirroring
src/server/test manual_compact_service_test (PEGASUS_UNIT_TEST mock time)."""

import pytest

from pegasus_tpu.base import consts
from pegasus_tpu.engine import EngineOptions
from pegasus_tpu.engine.manual_compact_service import GATE, ManualCompactService
from pegasus_tpu.engine.server_impl import PegasusServer


@pytest.fixture
def srv(tmp_path):
    s = PegasusServer(str(tmp_path / "db"), options=EngineOptions(backend="cpu"))
    yield s
    s.close()


def fill(srv, n=20):
    from pegasus_tpu.base import key_schema
    for i in range(n):
        srv.engine.put(key_schema.generate_key(b"h", b"s%03d" % i), b"\x82" + b"\0" * 12 + b"v")


def test_disabled_blocks_compaction(srv):
    svc = ManualCompactService(srv, mock_now=1000)
    envs = {consts.MANUAL_COMPACT_DISABLED_KEY: "true",
            consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "500"}
    assert not svc.start_manual_compact_if_needed(envs)


def test_once_trigger_fires_once(srv):
    fill(srv)
    svc = ManualCompactService(srv, mock_now=1000)
    envs = {consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "900"}
    assert svc.start_manual_compact_if_needed(envs)
    assert srv.engine.stats()["l0_files"] == 0
    # same trigger re-delivered: finish time newer -> no re-run
    svc.set_mock_now(2000)
    assert not svc.start_manual_compact_if_needed(envs)
    # a NEWER trigger fires again
    envs[consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY] = "1500"
    assert svc.start_manual_compact_if_needed(envs)


def test_once_trigger_in_future_does_not_fire(srv):
    svc = ManualCompactService(srv, mock_now=1000)
    envs = {consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "5000"}
    assert not svc.start_manual_compact_if_needed(envs)


def test_periodic_trigger(srv):
    import time as _time

    fill(srv)
    svc = ManualCompactService(srv)
    # build a local timestamp at 04:30 today
    now = _time.time()
    lt = _time.localtime(now)
    midnight = int(now) - (lt.tm_hour * 3600 + lt.tm_min * 60 + lt.tm_sec)
    svc.set_mock_now(midnight + 4 * 3600 + 30 * 60)
    envs = {consts.MANUAL_COMPACT_PERIODIC_TRIGGER_TIME_KEY: "3:00,21:00"}
    assert svc.start_manual_compact_if_needed(envs)   # 3:00 already passed
    assert not svc.start_manual_compact_if_needed(envs)  # not 21:00 yet
    svc.set_mock_now(midnight + 21 * 3600 + 60)
    assert svc.start_manual_compact_if_needed(envs)   # 21:00 passed


def test_concurrency_cap(srv, tmp_path):
    svc = ManualCompactService(srv, mock_now=1000)
    envs = {consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "900",
            consts.MANUAL_COMPACT_MAX_CONCURRENT_RUNNING_COUNT_KEY: "1"}
    GATE.running = 1  # someone else is compacting cluster-wide
    try:
        assert not svc.start_manual_compact_if_needed(envs)
    finally:
        GATE.running = 0
    assert svc.start_manual_compact_if_needed(envs)


def test_bottommost_and_target_level_opts(srv):
    fill(srv)
    svc = ManualCompactService(srv, mock_now=1000)
    envs = {
        consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "900",
        consts.MANUAL_COMPACT_ONCE_KEY_PREFIX
        + consts.MANUAL_COMPACT_TARGET_LEVEL_KEY: "1",
        consts.MANUAL_COMPACT_ONCE_KEY_PREFIX
        + consts.MANUAL_COMPACT_BOTTOMMOST_LEVEL_COMPACTION_KEY:
            consts.MANUAL_COMPACT_BOTTOMMOST_LEVEL_COMPACTION_FORCE,
    }
    assert svc.start_manual_compact_if_needed(envs)
    assert srv.engine.stats()["level_files"] == {1: 1}


def test_finish_time_persisted_and_state_string(srv):
    fill(srv)
    svc = ManualCompactService(srv, mock_now=1000)
    assert "never compacted" in svc.query_compact_state()
    svc.start_manual_compact_if_needed(
        {consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "900"})
    assert "idle; last finish" in svc.query_compact_state()
    assert srv.engine.meta_store[
        "pegasus_last_manual_compact_finish_time"] == 1000
    # a new service instance reads the persisted finish time
    svc2 = ManualCompactService(srv, mock_now=1000)
    assert svc2.last_finish_time_ms == 1000 * 1000


def test_app_env_update_path(srv):
    fill(srv)
    srv.manual_compact_service.set_mock_now(1000)
    srv.update_app_envs({consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: "900"})
    assert srv.engine.stats()["l0_files"] == 0


def test_env_update_compacts_in_the_background(srv, monkeypatch):
    """An app-env update (the meta's push behind the shell's
    `manual_compact`) returns once the env is accepted: the compaction runs
    on its own thread, its progress readable from query_compact_state."""
    import threading
    import time

    fill(srv)
    srv.engine.flush()
    svc = srv.manual_compact_service
    gate = threading.Event()
    real = srv.engine.manual_compact

    def slow_compact(**kw):
        gate.wait(10)
        return real(**kw)

    monkeypatch.setattr(srv.engine, "manual_compact", slow_compact)
    trigger = str(int(time.time()) - 1)
    t0 = time.monotonic()
    srv.update_app_envs({consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: trigger})
    assert time.monotonic() - t0 < 1.0   # did not wait for the compaction
    assert svc.query_compact_state().startswith(("queued", "running"))
    # the same env delivered again while it runs starts nothing new
    srv.update_app_envs({consts.MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY: trigger})
    gate.set()
    deadline = time.monotonic() + 10
    while not svc.query_compact_state().startswith("idle; last finish at"):
        assert time.monotonic() < deadline, svc.query_compact_state()
        time.sleep(0.02)
    assert srv.engine.stats()["l0_files"] == 0
