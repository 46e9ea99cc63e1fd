"""Compaction-pipeline telemetry tests: stage spans (nesting, ring-buffer
bounds, counter export), the device-health watchdog (timeout path with a
deliberately-hung fake backend, wedge-stage attribution), and the
/metrics + compact-trace-dump round trip against a running service app.
"""

import json
import threading
import time
import urllib.request

import pytest

from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.base.value_schema import SCHEMAS
from pegasus_tpu.engine.block import KVBlock
from pegasus_tpu.ops.device_watchdog import DeviceHealthWatchdog
from pegasus_tpu.runtime.perf_counters import counters
from pegasus_tpu.runtime.tracing import COMPACT_TRACER, StageTracer


def _make_block(n):
    return KVBlock.from_records(
        [(generate_key(b"h%d" % i, b"s"),
          SCHEMAS[2].generate_value(0, 0, b"v"), 0, False)
         for i in range(n)])


# --------------------------------------------------------------- span API


def test_span_nesting_records_depth_and_close_order():
    tr = StageTracer(prefix="t_nest")
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    rows = tr.trace()
    # children close before their parents; depth counts enclosing spans
    assert [(r["stage"], r["depth"]) for r in rows] == [
        ("inner", 1), ("inner2", 1), ("outer", 0)]
    assert all(r["duration_us"] >= 0 for r in rows)


def test_span_box_takes_mid_span_counts():
    tr = StageTracer(prefix="t_box")
    with tr.span("gather", records=1) as sp:
        sp["records"] = 41
        sp["bytes"] = 1000
    (row,) = tr.trace()
    assert row["records"] == 41 and row["bytes"] == 1000


def test_ring_buffer_bounded():
    tr = StageTracer(capacity=8, prefix="t_ring")
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    rows = tr.trace(last=1000)
    assert len(rows) == 8
    assert [r["stage"] for r in rows] == [f"s{i}" for i in range(42, 50)]
    # dump() renders every retained row
    assert tr.dump(1000).count("\n") == 7


def test_session_aggregates_per_stage():
    tr = StageTracer(prefix="t_sess")
    with tr.session() as sess:
        for _ in range(3):
            with tr.span("pack", records=10, nbytes=100):
                pass
        with tr.span("device", records=30):
            pass
    assert sess.stages["pack"]["calls"] == 3
    assert sess.stages["pack"]["records"] == 30
    assert sess.stages["pack"]["bytes"] == 300
    assert sess.stages["device"]["calls"] == 1
    summary = sess.summary()
    assert set(summary) == {"pack", "device"}
    assert summary["pack"]["s"] >= 0


def test_sessions_nest_and_are_thread_local():
    tr = StageTracer(prefix="t_tl")
    with tr.session() as outer:
        with tr.span("a"):
            pass
        with tr.session() as inner:
            with tr.span("b"):
                pass

            # a span closed on ANOTHER thread lands in neither session
            def other():
                with tr.span("c"):
                    pass

            t = threading.Thread(target=other)
            t.start()
            t.join()
    assert set(outer.stages) == {"a", "b"}
    assert set(inner.stages) == {"b"}
    stages = [r["stage"] for r in tr.trace()]
    assert "c" in stages  # the ring buffer itself is process-wide


def test_spans_export_rate_and_percentile_counters():
    tr = StageTracer(prefix="t_exp")
    with tr.span("device", records=7, nbytes=64):
        time.sleep(0.002)
    snap = counters.snapshot(prefix="t_exp.stage.device.")
    assert set(snap) == {"t_exp.stage.device.count",
                         "t_exp.stage.device.duration_us",
                         "t_exp.stage.device.records",
                         "t_exp.stage.device.bytes"}
    # the duration percentile keeps its sample (a rate would decay on read)
    assert counters.percentile(
        "t_exp.stage.device.duration_us").percentile(0.5) >= 2000


def test_open_stages_and_innermost_open():
    tr = StageTracer(prefix="t_open")
    release = threading.Event()
    entered = threading.Event()

    def worker():
        with tr.span("compact"):
            with tr.span("device"):
                entered.set()
                release.wait(10)

    t = threading.Thread(target=worker)
    t.start()
    try:
        assert entered.wait(10)
        (stack,) = tr.open_stages().values()
        assert stack == ["compact", "device"]
        stage, t0 = tr.innermost_open()
        assert stage == "device" and t0 <= time.time()
    finally:
        release.set()
        t.join()
    assert tr.open_stages() == {}
    assert tr.innermost_open() is None


def test_compact_pipeline_emits_stage_spans():
    """The real cpu pipeline threads pack/device/gather spans through the
    process-wide tracer — the breakdown manual_compact's stats carry."""
    from pegasus_tpu.ops import CompactOptions, compact_blocks

    blk = _make_block(64)
    with COMPACT_TRACER.session() as sess:
        res = compact_blocks([blk], CompactOptions(backend="cpu", now=100))
    assert res.block.n == 64
    for stage in ("compact", "pack", "device", "gather"):
        assert stage in sess.stages, f"missing {stage}: {sess.summary()}"
    assert sess.stages["compact"]["records"] == 64
    assert sess.stages["pack"]["bytes"] > 0


# --------------------------------------------------------------- watchdog


def test_watchdog_ok_probe_records_last_ok():
    wd = DeviceHealthWatchdog(probe_fn=lambda: True,
                              tracer=StageTracer(prefix="t_wd0"))
    assert wd.probe() is True
    st = wd.state()
    assert st["last_ok"] is not None
    assert st["wedged_at_stage"] is None and st["last_error"] is None
    assert counters.number("compact.watchdog.wedged").value() == 0


def test_watchdog_timeout_attributes_wedged_stage():
    """A deliberately-hung fake backend: the probe must time out (not
    hang), refuse to stack a second probe behind the hung one, attribute
    the wedge to the innermost open span only once fail_threshold
    CONSECUTIVE probes failed (one starved probe is an error, not a
    wedge), and recover once the backend unwedges."""
    tr = StageTracer(prefix="t_wd1")
    hang = threading.Event()
    entered = threading.Event()
    wd = DeviceHealthWatchdog(probe_timeout_s=0.2, tracer=tr,
                              probe_fn=lambda: hang.wait(30) or True,
                              fail_threshold=2)

    def pipeline():
        with tr.span("compact"):
            with tr.span("h2d"):
                entered.set()
                hang.wait(30)

    t = threading.Thread(target=pipeline, daemon=True)
    t.start()
    try:
        assert entered.wait(10)
        t0 = time.monotonic()
        assert wd.probe() is False
        assert time.monotonic() - t0 < 5  # bounded, never the probe's 30s
        st = wd.state()
        # one failure is an error, NOT yet a wedge verdict (threshold=2)
        assert st["wedged_at_stage"] is None
        assert "timed out" in st["last_error"]
        assert ["compact", "h2d"] in st["open_stages"].values()
        # the first probe's thread is still wedged: fail fast, don't
        # stack — and the SECOND consecutive failure flips the verdict,
        # attributed to the innermost open span
        assert wd.probe() is False
        st = wd.state()
        assert "still hung" in st["last_error"]
        assert st["wedged_at_stage"] == "h2d"
        assert counters.number("compact.watchdog.wedged").value() == 1
    finally:
        hang.set()
        t.join()
    deadline = time.monotonic() + 10  # let the abandoned probe drain
    while wd.probe() is not True:
        assert time.monotonic() < deadline, wd.state()
        time.sleep(0.05)
    st = wd.state()
    assert st["wedged_at_stage"] is None and st["last_ok"] is not None


def test_watchdog_idle_attribution():
    wd = DeviceHealthWatchdog(probe_timeout_s=0.1,
                              tracer=StageTracer(prefix="t_wd2"),
                              probe_fn=lambda: threading.Event().wait(30),
                              fail_threshold=1)
    assert wd.probe() is False
    assert wd.state()["wedged_at_stage"] == "idle"


def test_watchdog_probe_error_is_a_failure_not_a_crash():
    def boom():
        raise RuntimeError("device reset")

    wd = DeviceHealthWatchdog(probe_fn=boom,
                              tracer=StageTracer(prefix="t_wd3"))
    assert wd.probe() is False
    assert "device reset" in wd.state()["last_error"]


# ---------------------------------------------- service-app round trip


@pytest.fixture
def service_pair(tmp_path):
    from pegasus_tpu.runtime.config import Config
    from pegasus_tpu.runtime.service_app import MetaApp, ReplicaApp

    ini = tmp_path / "app.ini"
    ini.write_text(f"""
[apps.meta]
type = meta
port = 0
state_dir = {tmp_path}/meta
http_port = 0

[apps.replica1]
type = replica
port = 0
data_dir = {tmp_path}/replica1
http_port = 0

[pegasus.server]
meta_servers = 127.0.0.1:0

[failure_detector]
beacon_interval_seconds = 0.2
""")
    cfg = Config(str(ini))
    meta_app = MetaApp("meta", cfg, "apps.meta")
    meta_app.start()
    cfg._parser.set("pegasus.server", "meta_servers", meta_app.address)
    rep_app = ReplicaApp("replica1", cfg, "apps.replica1").start()
    try:
        yield meta_app, rep_app
    finally:
        rep_app.stop()
        meta_app.stop()


def _http_get(reporter, path):
    host, port = reporter.address
    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=5) as r:
        return r.read().decode()


def _seed_pipeline_counters(tmp_path):
    """Run the real cpu pipeline + an sst write so the process-wide
    registry holds compact.* and engine.* counters to scrape."""
    from pegasus_tpu.engine.sstable import write_sst
    from pegasus_tpu.ops import CompactOptions, compact_blocks

    blk = _make_block(32)
    res = compact_blocks([blk], CompactOptions(backend="cpu", now=100))
    write_sst(str(tmp_path / "seed.sst"), res.block)


def test_metrics_route_serves_compact_and_engine_counters(
        service_pair, tmp_path):
    """Acceptance: GET /metrics on a replica app serves Prometheus text
    including engine.* and compact.* counters (dots mangled to '_')."""
    _, rep_app = service_pair
    _seed_pipeline_counters(tmp_path)
    body = _http_get(rep_app.reporter, "/metrics")
    assert "# TYPE compact_stage_pack_count gauge" in body
    assert "compact_stage_device_count" in body
    assert "compact_stage_gather_count" in body
    assert "engine_sst_write_count" in body
    for line in body.splitlines():
        if not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            float(value)  # every sample line is name SP float


def test_compact_trace_routes_and_remote_command(service_pair, tmp_path):
    """The three trace surfaces read one tracer: the /compact/trace HTTP
    route (meta + replica), the compact-trace-dump remote command, and
    device-health — all reporting the spans the pipeline just emitted."""
    from pegasus_tpu.rpc import codec
    from pegasus_tpu.rpc.transport import RpcConnection
    from pegasus_tpu.runtime.remote_command import (RemoteCommandRequest,
                                                    RemoteCommandResponse)

    meta_app, rep_app = service_pair
    _seed_pipeline_counters(tmp_path)

    for reporter in (meta_app.reporter, rep_app.reporter):
        out = json.loads(_http_get(reporter, "/compact/trace?last=500"))
        stages = {s["stage"] for s in out["spans"]}
        assert {"pack", "device", "gather"} <= stages
        assert "wedged_at_stage" in out["watchdog"]
    # ?last=N bounds the dump
    out = json.loads(_http_get(rep_app.reporter, "/compact/trace?last=2"))
    assert len(out["spans"]) == 2

    host, _, port = rep_app.address.rpartition(":")
    conn = RpcConnection((host, int(port)))
    try:
        def cli(cmd, *args):
            _, body = conn.call("RPC_CLI_CLI_CALL", codec.encode(
                RemoteCommandRequest(cmd, list(args))), timeout=10)
            return codec.decode(RemoteCommandResponse, body).output

        dump = cli("compact-trace-dump", "500")
        assert "pack" in dump and "device" in dump and "gather" in dump
        health = json.loads(cli("device-health"))
        assert "last_ok" in health and "wedged_at_stage" in health
        # the same registry the /metrics route serves
        snap = json.loads(cli("perf-counters-by-prefix", "compact.stage."))
        assert any(k.startswith("compact.stage.pack.") for k in snap)
    finally:
        conn.close()
