"""Bring-up contracts (ISSUE 21): nothing may hide which device the kernels
run on, the compile cache is placed from outside, and chip_smoke.py's CPU
rehearsal stays runnable (so a broken smoke is found here, not on chip
time)."""

import json
import os
import subprocess
import sys

import jax
import pytest

from pegasus_tpu.base import utils

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


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


# --------------------------------------------------------------- chip_smoke


def _run_smoke(args, timeout_s):
    proc = subprocess.run([sys.executable, SMOKE] + args, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout_s)
    return proc.returncode, proc.stdout, proc.stderr


def test_chip_smoke_without_rehearsal_refuses_a_cpu():
    """No TPU here and no --cpu-rehearsal: the smoke exits non-zero before
    loading anything and prints no result line."""
    rc, out, err = _run_smoke(["--phases", "serve"], timeout_s=240)
    assert rc != 0
    assert not out.strip().splitlines()[-1].startswith("{")
    assert "not on a TPU" in err
    assert "loaded" not in out


def test_chip_smoke_cpu_rehearsal_serves_and_checks():
    """The serve phase end to end at a few thousand records on XLA:CPU:
    boots the real server from the derived ini, loads through the client,
    compacts through the shell, compares every read with the reference,
    audits three replicas, and scrapes the device proof."""
    rc, out, err = _run_smoke(["--cpu-rehearsal", "--phases", "serve"],
                              timeout_s=300)
    assert rc == 0, (out[-1500:], err[-1500:])
    final = json.loads(out.strip().splitlines()[-1])
    assert final["ok"] is True and final["chip"] is False
    assert final["device"]["platform"] == "cpu"
    assert "reads byte-equal to the reference" in out
    assert "shell: use smoke + manual_compact -> manual compact triggered" \
        in out
    assert "shell: trigger_audit smoke -> audit OK: 4 partition(s)" in out


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_every_phase():
    rc, out, err = _run_smoke(["--cpu-rehearsal"], timeout_s=900)
    assert rc == 0, (out[-1500:], err[-1500:])
    assert "[compact] PASS" in out and "the second added no kernel entry" in out
    # the suite's XLA_FLAGS give the child 8 virtual devices: mesh runs
    assert "[mesh] PASS" in out or "[mesh] skipped" in out
