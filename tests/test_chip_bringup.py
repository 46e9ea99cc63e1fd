"""Bring-up contracts (ISSUE 21): nothing may hide which device the kernels
run on, and the compile cache is placed from outside."""

import os

import jax
import pytest

from pegasus_tpu.base import utils

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ compile cache


@pytest.fixture
def cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_dir_left_alone_when_placed_from_outside(
        monkeypatch, cache_config, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, jax reads it itself and no code
    here sets another directory."""
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "placed"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    utils.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "placed")


def test_compile_cache_dir_defaults_to_the_checkout(monkeypatch, cache_config,
                                                    tmp_path):
    """Unset, it is <checkout>/.jax_cache — a fixed path, never one derived
    from a temp dir, pid or clock (the path is part of the cache key)."""
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "other"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    utils.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO,
                                                                ".jax_cache")


# ------------------------------------------------------------ no silent CPU


@pytest.fixture
def fresh_gate(monkeypatch):
    """open_device_backend resolves once per process: give the test its
    own unresolved gate (restored afterwards)."""
    monkeypatch.setattr(utils, "_DEVICE", None)


@pytest.mark.parametrize("platforms", [None, "tpu", "tpu,cpu"])
def test_tpu_engine_without_a_tpu_raises_naming_the_platform(
        monkeypatch, fresh_gate, tmp_path, platforms):
    """backend="tpu" on a host where jax resolved the cpu, with no explicit
    request for it: the engine refuses to open and says what it found."""
    from pegasus_tpu.engine import EngineOptions, LsmEngine

    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match=r"needs a TPU.*'cpu'"):
        LsmEngine(str(tmp_path / "e"), EngineOptions(backend="tpu"))
    assert utils.device_report()["device"] is None


def test_explicit_cpu_platform_opens_and_is_reported(monkeypatch, fresh_gate,
                                                     tmp_path):
    """JAX_PLATFORMS=cpu, set explicitly, is how tests and rehearsals run
    the device kernels on XLA:CPU — and device-health says so."""
    from pegasus_tpu.engine import EngineOptions, LsmEngine
    from pegasus_tpu.ops.device_watchdog import BYPASS_COUNTERS, WATCHDOG

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    LsmEngine(str(tmp_path / "e"), EngineOptions(backend="tpu")).close()
    health = WATCHDOG.state()
    assert health["device"]["platform"] == "cpu"
    assert health["device"]["device_count"] == len(jax.devices())
    assert health["device"]["jax"] == jax.__version__
    assert health["compile_cache_dir"] == jax.config.jax_compilation_cache_dir
    # both lanes' totals, the compile totals and every quiet-bypass counter
    # ride along
    for lane in ("lane", "read_lane"):
        assert "fallbacks" in health[lane] and "retries" in health[lane]
        assert "compile_behind" in health[lane]
    assert set(health["compile"]) == {"compiled", "failed", "inflight",
                                      "seconds", "max_s", "kernels"}
    assert set(health["bypass"]) == set(BYPASS_COUNTERS)


def test_long_key_run_bypass_is_counted():
    """A run holding a key over the window's 64 B cap is refused HBM
    residency (production policy) — and that is countable. (Up to the cap
    the window follows the run's longest key: tests/test_geo_deployment.py.)"""
    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.ops.compact import pack_run_device
    from pegasus_tpu.runtime.perf_counters import counters

    c = counters.number("engine.hbm.long_key_bypass_count")
    before = c.value()
    blk = KVBlock.from_records([(b"\x00\x02hk" + b"s" * 80, b"v", 0, False)])
    assert pack_run_device(blk) is None
    assert c.value() == before + 1
