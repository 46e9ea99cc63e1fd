"""Ecosystem-layer tests: service-app container, shell, collector,
reporter, hotkey detection — driven against a real in-process onebox."""

import io
import json
import time
import urllib.request

import pytest

from pegasus_tpu.collector import (AvailableDetector, CounterReporter,
                                   InfoCollector, hotspot_partitions,
                                   prometheus_text)
from pegasus_tpu.engine.hotkey_collector import (COARSE, FINE, FINISHED,
                                                 HotkeyCollector, STOPPED)
from pegasus_tpu.runtime.config import Config
from pegasus_tpu.runtime.service_app import ServiceAppContainer
from pegasus_tpu.shell.main import Shell

ONEBOX_INI = """
[apps.meta]
type = meta
run = true
port = 0
state_dir = %{root}/meta

[apps.replica1]
type = replica
run = true
port = 0
data_dir = %{root}/replica1

[apps.replica2]
type = replica
run = true
port = 0
data_dir = %{root}/replica2

[apps.replica3]
type = replica
run = true
port = 0
data_dir = %{root}/replica3

[pegasus.server]
meta_servers = %{meta}

[failure_detector]
beacon_interval_seconds = 0.2
grace_seconds = 60
check_interval_seconds = 3600
"""


@pytest.fixture(scope="module")
def onebox(tmp_path_factory):
    root = tmp_path_factory.mktemp("toolbox")
    cfg_meta = Config(text=ONEBOX_INI, variables={"root": str(root), "meta": "x"})
    container = ServiceAppContainer(cfg_meta)
    container.start(only=["meta"])
    meta_addr = container.apps["meta"].address
    cfg_rest = Config(text=ONEBOX_INI,
                      variables={"root": str(root), "meta": meta_addr})
    container2 = ServiceAppContainer(cfg_rest)
    container2.start(only=["replica1", "replica2", "replica3"])
    time.sleep(0.3)  # beacons land
    yield meta_addr
    container2.stop()
    container.stop()


@pytest.fixture
def shell(onebox):
    out = io.StringIO()
    sh = Shell([onebox], out=out)
    return sh, out


def text(out):
    return out.getvalue()


def test_shell_ddl_and_data_ops(shell):
    sh, out = shell
    sh.run_line("create shelltest -p 4 -r 3")
    assert "succeed" in text(out)
    sh.run_line("use shelltest")
    sh.run_line("ls")
    assert "shelltest" in text(out)
    sh.run_line("app shelltest")
    assert "pidx" in text(out)
    sh.run_line('set user1 sk1 "hello world"')
    sh.run_line("get user1 sk1")
    assert "hello world" in text(out)
    sh.run_line("exist user1 sk1")
    sh.run_line("ttl user1 sk1")
    assert "no ttl" in text(out)
    sh.run_line("incr user1 counter 5")
    sh.run_line("multi_set mh a 1 b 2 c 3")
    sh.run_line("multi_get mh")
    assert '"a" : "1"' in text(out)
    sh.run_line("sortkey_count mh")
    sh.run_line("hash_scan mh")
    sh.run_line("multi_del mh a b")
    sh.run_line("del user1 sk1")
    sh.run_line("get user1 sk1")
    assert "not found" in text(out)


def test_shell_cluster_admin(shell):
    sh, out = shell
    sh.run_line("cluster_info")
    assert "node_count" in text(out)
    sh.run_line("nodes")
    assert "ALIVE" in text(out)
    sh.run_line("server_info")
    assert "pegasus-tpu" in text(out)
    sh.run_line("server_stat")


def test_shell_full_scan_and_copy(shell):
    sh, out = shell
    sh.run_line("create copysrc -p 2")
    sh.run_line("create copydst -p 2")
    sh.run_line("use copysrc")
    for i in range(6):
        sh.run_line(f"set h{i} s v{i}")
    sh.run_line("count_data")
    assert "6 rows" in text(out)
    sh.run_line("copy_data copydst")
    assert "copied 6 rows" in text(out)
    sh.run_line("use copydst")
    sh.run_line("get h3 s")
    assert "v3" in text(out)
    sh.run_line("full_scan")


def test_shell_envs_and_manual_compact(shell):
    sh, out = shell
    sh.run_line("create envtest -p 2")
    sh.run_line("use envtest")
    sh.run_line("set k s v")
    sh.run_line("set_app_envs rocksdb.usage_scenario prefer_write")
    assert "set 1 envs OK" in text(out)
    sh.run_line("get_app_envs")
    assert "prefer_write" in text(out)
    sh.run_line("manual_compact")
    assert "triggered" in text(out)
    sh.run_line("query_compact_state")
    assert "idle" in text(out) or "running" in text(out)


def test_shell_trigger_audit_sizes_its_timeout(shell, monkeypatch):
    """`trigger_audit [app [timeout_s]]`: without an explicit timeout the
    shell sizes it by the largest replica on disk (every replica folds
    every live record into its digest), and the verdict still prints."""
    sh, out = shell
    sh.run_line("create audsz -p 2")
    sh.run_line("use audsz")
    sh.run_line("set k s v")
    assert 5.0 <= sh._audit_timeout_s() < 6.0   # a tiny table: the 5 s floor
    real = sh._node_command

    def big_disk(node, command, args):
        if command == "replica-disk":
            return json.dumps({"9.0": {"sst_bytes": 400 << 20}})
        return real(node, command, args)

    monkeypatch.setattr(sh, "_node_command", big_disk)
    assert sh._audit_timeout_s() == 105.0       # 5 s + 400 MB at 4 MB/s
    sh.run_line("trigger_audit audsz")
    assert "audit OK: 2 partition(s)" in text(out)
    sh.run_line("trigger_audit audsz 30")
    assert text(out).count("audit OK: 2 partition(s)") == 2


def test_shell_remote_and_counters(shell, onebox):
    sh, out = shell
    sh.run_line("create cnttest -p 2")
    sh.run_line("use cnttest")
    sh.run_line("set hot s v")
    nodes = [n.address for n in sh._nodes() if n.alive]
    sh.run_line(f"perf_counters {nodes[0]} app.")
    sh.run_line("remote_command all describe")
    assert "replicas" in text(out)


def test_hotkey_state_machine():
    hc = HotkeyCollector("read", coarse_threshold=50, fine_threshold=30)
    assert hc.state == STOPPED
    hc.start()
    assert hc.state == COARSE
    # one dominant key among background noise
    for i in range(200):
        hc.capture(b"HOT" if i % 2 == 0 else b"bg%d" % i)
    assert hc.state == FINISHED
    assert hc.result == b"HOT"
    assert b"HOT" in hc.query().encode()
    hc.stop()
    assert hc.state == STOPPED


def test_hotkey_uniform_load_finds_nothing():
    hc = HotkeyCollector("write", coarse_threshold=50)
    hc.start()
    hc.max_seconds = 0.0
    hc._deadline = 0.0  # already past: next capture must self-terminate
    hc.capture(b"k")
    assert "STOPPED" in hc.query()
    hc = HotkeyCollector("write", coarse_threshold=50)
    hc.start()
    for i in range(300):
        hc.capture(b"k%d" % i)
    assert hc.state in (COARSE, FINE)  # never FINISHED on uniform load


def test_detect_hotkey_via_shell(shell):
    sh, out = shell
    sh.run_line("create hottest -p 1 -r 3")
    sh.run_line("use hottest")
    cfg = sh._meta_call.__self__  # noqa: simple access below instead
    # find the node serving partition 0
    import pegasus_tpu.meta.messages as mm
    from pegasus_tpu.meta.meta_server import RPC_CM_QUERY_CONFIG

    qc = sh._meta_call(RPC_CM_QUERY_CONFIG, mm.QueryConfigRequest("hottest"),
                       mm.QueryConfigResponse)
    node = qc.partitions[0].primary
    app_id = qc.app.app_id
    sh.run_line(f"detect_hotkey {node} {app_id}.0 read start")
    assert "started" in text(out)
    for i in range(300):
        sh.run_line("get hotkey1 s" if i % 2 == 0 else f"get cold{i} s")
    sh.run_line(f"detect_hotkey {node} {app_id}.0 read query")
    assert "hotkey1" in text(out)


def test_hotspot_partition_analysis():
    qps = {i: 10.0 for i in range(8)}
    assert hotspot_partitions(qps) == []
    qps[3] = 500.0
    assert hotspot_partitions(qps) == [3]


class _FakeHotkeyNode:
    """Scripted detect_hotkey endpoint for the closed-loop driver."""

    def __init__(self, answers):
        self.answers = list(answers)
        self.calls = []

    def remote_command(self, addr, command, args):
        if command == "set-read-residency":
            # a read verdict drives the partition's device read residency
            # (PR 7); recorded like every other call
            self.calls.append((addr, (command,) + tuple(args)))
            return f"read residency {args[1]} for {args[0]}"
        assert command == "detect_hotkey"
        self.calls.append((addr, tuple(args)))
        action = args[2]
        if action == "start":
            return "read hotkey detection started (coarse)"
        if action == "stop":
            return "read hotkey detection stopped"
        return self.answers.pop(0)


def test_hotkey_loop_state_machine():
    """A partition flagged hotkey_rounds consecutive rounds gets the
    automatic detect_hotkey start/query/stop sequence; the verdict is
    republished as collector.app.<name>.hotkey.* counters."""
    from pegasus_tpu.runtime.perf_counters import counters

    coll = InfoCollector(["x:1"], hotkey_rounds=2)
    fake = _FakeHotkeyNode(["read detection state: FINE_DETECTING",
                            "read hotkey: b'HOT'"])
    coll.remote_command = fake.remote_command
    primaries = {3: "node-a:34801"}
    # round 1: flagged, streak below threshold -> nothing issued
    coll.drive_hotkey_loop("happ", 9, [3], primaries, {3: 100.0}, {3: 1.0})
    assert fake.calls == []
    # round 2: streak reaches 2 -> start (read kind: read qps dominates),
    # the first query follows in the same round and is unconverged
    coll.drive_hotkey_loop("happ", 9, [3], primaries, {3: 100.0}, {3: 1.0})
    assert fake.calls[0] == ("node-a:34801", ("9.3", "read", "start"))
    assert fake.calls[-1][1] == ("9.3", "read", "query")
    assert ("happ", 3) in coll._detections
    # round 3: verdict -> republished, detection stopped, streak cleared
    coll.drive_hotkey_loop("happ", 9, [3], primaries, {3: 100.0}, {3: 1.0})
    assert fake.calls[-1][1] == ("9.3", "read", "stop")
    assert ("happ", 3) not in coll._detections
    assert coll.hotkey_results["happ"][3]["key"] == "b'HOT'"
    assert coll.hotkey_results["happ"][3]["kind"] == "read"
    snap = counters.snapshot(prefix="collector.app.happ.hotkey.")
    assert snap["collector.app.happ.hotkey.3.hot"] == 1
    assert snap["collector.app.happ.hotkey.active_detections"] == 0
    assert snap["collector.app.happ.hotkey.found_count"] > 0
    # the read verdict drove the partition's device read residency on
    assert ("node-a:34801", ("set-read-residency", "9.3", "on")) in fake.calls
    assert ("happ", 3) in coll.read_residency
    # the partition calms: the verdict gauge must clear, not page forever
    # — and the residency pin is released with it
    coll.drive_hotkey_loop("happ", 9, [], primaries)
    snap = counters.snapshot(prefix="collector.app.happ.hotkey.")
    assert snap["collector.app.happ.hotkey.3.hot"] == 0
    assert ("node-a:34801", ("set-read-residency", "9.3", "off")) in fake.calls
    assert ("happ", 3) not in coll.read_residency
    coll.stop()


def test_hotkey_loop_survives_dead_or_moved_primary():
    """An unreachable node must not pin a detection forever (failed query
    rounds burn the query budget), and a moved primary abandons the
    detection so a fresh streak can restart it on the new node."""
    from pegasus_tpu.rpc.transport import RpcError

    coll = InfoCollector(["x:1"], hotkey_rounds=1, hotkey_query_limit=2)

    calls = []

    def unreachable(addr, command, args):
        calls.append(tuple(args))
        if args[2] == "start":
            return "read hotkey detection started (coarse)"
        raise RpcError(7, "connection refused")

    coll.remote_command = unreachable
    primaries = {0: "dead-node:1"}
    coll.drive_hotkey_loop("dapp", 4, [0], primaries)   # start + failed query
    assert ("dapp", 0) in coll._detections
    coll.drive_hotkey_loop("dapp", 4, [0], primaries)   # failed query 2
    coll.drive_hotkey_loop("dapp", 4, [0], primaries)   # over budget: expire
    assert ("dapp", 0) not in coll._detections

    # primary move: detection abandoned (stop goes to the OLD node)
    coll2 = InfoCollector(["x:1"], hotkey_rounds=1)
    fake = _FakeHotkeyNode(["read detection state: COARSE_DETECTING"])
    coll2.remote_command = fake.remote_command
    coll2.drive_hotkey_loop("mapp", 6, [0], {0: "node-a:1"})
    assert ("mapp", 0) in coll2._detections
    coll2.drive_hotkey_loop("mapp", 6, [0], {0: "node-b:1"})
    assert ("mapp", 0) not in coll2._detections
    assert fake.calls[-1] == ("node-a:1", ("6.0", "read", "stop"))
    coll.stop()
    coll2.stop()


def test_hotkey_loop_streak_resets_when_calm():
    coll = InfoCollector(["x:1"], hotkey_rounds=3)
    fake = _FakeHotkeyNode(["write detection state: COARSE_DETECTING"])
    coll.remote_command = fake.remote_command
    primaries = {0: "n:1"}
    coll.drive_hotkey_loop("capp", 5, [0], primaries)
    coll.drive_hotkey_loop("capp", 5, [0], primaries)
    coll.drive_hotkey_loop("capp", 5, [], primaries)   # calm round resets
    coll.drive_hotkey_loop("capp", 5, [0], primaries)
    coll.drive_hotkey_loop("capp", 5, [0], primaries)
    assert fake.calls == []  # never reached 3 consecutive rounds
    # write-dominant partitions get a write-kind detection
    coll.drive_hotkey_loop("capp", 5, [0], primaries, {0: 1.0}, {0: 50.0})
    assert fake.calls[0][1] == ("5.0", "write", "start")
    coll.stop()


def test_hotkey_loop_closed_against_live_node(shell):
    """End to end: the driver starts a REAL detection on the node serving
    the partition, hot traffic converges it, the next round publishes the
    verdict."""
    sh, out = shell
    sh.run_line("create hotloop -p 1 -r 3")
    sh.run_line("use hotloop")
    import pegasus_tpu.meta.messages as mm
    from pegasus_tpu.meta.meta_server import RPC_CM_QUERY_CONFIG

    qc = sh._meta_call(RPC_CM_QUERY_CONFIG, mm.QueryConfigRequest("hotloop"),
                       mm.QueryConfigResponse)
    node, app_id = qc.partitions[0].primary, qc.app.app_id
    coll = InfoCollector(sh.meta_addrs, hotkey_rounds=1)
    try:
        coll.drive_hotkey_loop("hotloop", app_id, [0], {0: node},
                               {0: 500.0}, {0: 1.0})
        assert ("hotloop", 0) in coll._detections
        for i in range(300):  # one dominant key among noise
            sh.run_line("get hotkey1 s" if i % 2 == 0 else f"get cold{i} s")
        coll.drive_hotkey_loop("hotloop", app_id, [0], {0: node},
                               {0: 500.0}, {0: 1.0})
        assert coll.hotkey_results["hotloop"][0]["key"].startswith("b'hotkey1")
    finally:
        coll.stop()


def test_metric_names_lint_clean():
    """tools/check_metric_names.py wired into the test run: every counter
    name registered in source is documented in README.md's metric table."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "tools" / "check_metric_names.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_remote_commands_lint_clean():
    """tools/check_remote_commands.py wired into the test run: every
    registered remote command is documented in README.md's
    Remote-command table, and every table row still names a registered
    command (both directions, like the fail-point lint)."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "tools" / "check_remote_commands.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_remote_commands_lint_flags_undocumented(monkeypatch):
    """Both lint directions have teeth: an unregistered README row and an
    undocumented registration each produce an error."""
    from tools import check_remote_commands as cc

    real_src = cc.source_commands()
    monkeypatch.setattr(cc, "source_commands",
                        lambda: real_src | {"ghost-command"})
    errors = cc.run_lint()
    assert any("ghost-command" in e and "missing from README" in e
               for e in errors)
    monkeypatch.setattr(cc, "source_commands",
                        lambda: real_src - {"cluster-doctor"})
    errors = cc.run_lint()
    assert any("cluster-doctor" in e and "no matching registration" in e
               for e in errors)


def test_counter_reporter_prometheus(onebox):
    from pegasus_tpu.runtime.perf_counters import counters

    counters.number("reporter.test_metric").set(42)
    rep = CounterReporter().start()
    try:
        host, port = rep.address
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5).read().decode()
        assert "reporter_test_metric 42.0" in body
        cjson = urllib.request.urlopen(
            f"http://{host}:{port}/counters", timeout=5).read().decode()
        assert json.loads(cjson)["reporter.test_metric"] == 42
    finally:
        rep.stop()


def test_info_collector_aggregates(onebox, shell):
    sh, out = shell
    sh.run_line("create colltest -p 2")
    sh.run_line("use colltest")
    for i in range(10):
        sh.run_line(f"set ck{i} s v")
        sh.run_line(f"get ck{i} s")
    coll = InfoCollector([onebox], interval_seconds=3600)
    summary = coll.collect_once()
    assert "colltest" in summary
    assert summary["colltest"]["get_qps"] >= 0
    coll.stop()


def test_available_detector_probe(onebox, shell):
    sh, _ = shell
    sh.run_line("create test -p 2")  # the canary's default table
    det = AvailableDetector([onebox], interval_seconds=3600)
    assert det.probe_once() is True
    rep = det.report()
    assert rep["minute"] == 1.0
    det.stop()


def test_toollets_trace_profile_inject(onebox, shell):
    from pegasus_tpu.runtime import fail_points
    from pegasus_tpu.runtime.perf_counters import counters
    from pegasus_tpu.runtime.toollets import install_toollets
    from pegasus_tpu.rpc.transport import RpcServer, RpcConnection, RpcError
    from pegasus_tpu.runtime.remote_command import RemoteCommandService

    srv = RpcServer().start()
    cmds = RemoteCommandService()
    srv.register("RPC_TEST_ECHO", lambda h, b: b)
    srv.register("RPC_CLI_CLI_CALL", cmds.rpc_handler)
    tools = install_toollets(srv, ["tracer", "profiler", "fault_injector"],
                             command_service=cmds)
    conn = RpcConnection(srv.address)
    try:
        _, out = conn.call("RPC_TEST_ECHO", b"hello", timeout=5)
        assert out == b"hello"
        assert counters.snapshot()["profiler.RPC_TEST_ECHO.qps"] >= 0
        assert "RPC_TEST_ECHO" in tools["tracer"].dump()
        # fault injection drops the call
        fail_points.setup()
        fail_points.cfg("rpc.RPC_TEST_ECHO", "return()")
        import pytest as _pytest
        with _pytest.raises(RpcError):
            conn.call("RPC_TEST_ECHO", b"x", timeout=5)
        fail_points.teardown()
        _, out = conn.call("RPC_TEST_ECHO", b"ok", timeout=5)
        assert out == b"ok"
    finally:
        conn.close()
        srv.stop()


def test_slow_query_log_and_counter(tmp_path, capsys):
    from pegasus_tpu.base import consts, key_schema
    from pegasus_tpu.engine import EngineOptions
    from pegasus_tpu.engine.server_impl import PegasusServer
    from pegasus_tpu.runtime.perf_counters import counters

    srv = PegasusServer(str(tmp_path / "sq"), app_id=99, pidx=0,
                        options=EngineOptions(backend="cpu"))
    srv.update_app_envs({consts.ENV_SLOW_QUERY_THRESHOLD: "0"})
    srv.on_get(key_schema.generate_key(b"h", b"s"))
    # threshold 0 disables the log entirely
    assert "app.99.0.recent_abnormal_count" not in counters.snapshot()
    # a sub-microsecond threshold flags every get
    srv._app_envs[consts.ENV_SLOW_QUERY_THRESHOLD] = "-1"
    srv._check_slow_query("get", b"h", elapsed_us=50_000)  # forced sample
    srv.update_app_envs({consts.ENV_SLOW_QUERY_THRESHOLD: "1"})
    srv._check_slow_query("get", b"h", elapsed_us=50_000)
    assert counters.snapshot()["app.99.0.recent_abnormal_count"] >= 0
    assert "[slow-query]" in capsys.readouterr().out
    srv.close()


def test_offline_debuggers(tmp_path, shell):
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import SCHEMAS
    from pegasus_tpu.engine.db import EngineOptions, LsmEngine
    from pegasus_tpu.replication.mutation_log import LogMutation, MutationLog

    sh, out = shell
    eng = LsmEngine(str(tmp_path / "ldb"), EngineOptions(backend="cpu"))
    for i in range(5):
        eng.put(generate_key(b"oh", b"s%d" % i),
                SCHEMAS[2].generate_value(0, 0, b"val%d" % i))
    eng.flush()
    sst = eng._l0[0].path
    sh.run_line(f"sst_dump {sst}")
    assert "records=5" in text(out)
    sh.run_line(f'local_get {tmp_path / "ldb"} oh s2')
    assert "val2" in text(out)
    log = MutationLog(str(tmp_path / "plog"))
    log.append(LogMutation(decree=1, codes=["RPC_RRDB_RRDB_PUT"], bodies=[b"x"]))
    log.close()
    sh.run_line(f'mlog_dump {tmp_path / "plog"}')
    assert "decree=1" in text(out)


def test_client_factory_singleton(onebox, shell):
    from pegasus_tpu.client import get_client

    sh, _ = shell
    sh.run_line("create facttest -p 2")
    c1 = get_client(onebox, "facttest")
    c2 = get_client([onebox], "facttest")
    assert c1 is c2
    c1.set(b"f", b"s", b"v")
    assert c2.get(b"f", b"s") == b"v"


def test_block_service_local_provider(tmp_path):
    from pegasus_tpu.runtime.block_service import create_block_service

    bs = create_block_service("local_service", str(tmp_path / "store"))
    src = tmp_path / "f.txt"
    src.write_bytes(b"hello")
    bs.upload(str(src), "backups/1/f.txt")
    assert bs.exists("backups/1/f.txt")
    assert bs.read("backups/1/f.txt") == b"hello"
    assert bs.list_dir("backups/1") == ["f.txt"]
    dst = tmp_path / "out" / "f.txt"
    bs.download("backups/1/f.txt", str(dst))
    assert dst.read_bytes() == b"hello"
    bs.write("direct/x.bin", b"\x00\x01")
    assert bs.read("direct/x.bin") == b"\x00\x01"
    import pytest as _p
    with _p.raises(ValueError):
        bs.upload(str(src), "../escape.txt")


def test_throttling_controller_parse_and_consume():
    from pegasus_tpu.engine.throttling import (ThrottleReject,
                                               ThrottlingController)

    t = ThrottlingController()
    assert t.parse_from_env("5*delay*0,8*reject*0")
    for _ in range(5):
        t.consume(1)          # under both thresholds
    t.consume(1)              # 6th: delayed (0ms — just counted)
    assert t.delayed_count == 1
    for _ in range(2):
        t.consume(1)
    try:
        t.consume(1)          # 9th: past reject threshold
        raise AssertionError("expected ThrottleReject")
    except ThrottleReject:
        pass
    assert t.rejected_count == 1
    # bare number = reject-only; malformed input keeps the old setting
    assert t.parse_from_env("3")
    assert t.reject_units == 3 and t.delay_units == 0
    assert not t.parse_from_env("nonsense*x*1")
    assert t.reject_units == 3
    assert t.parse_from_env("")   # empty disables
    assert not t.enabled


def test_metric_lint_reverse_pass_flags_stale_rows(monkeypatch):
    """The reverse direction of tools/check_metric_names.py: README rows
    parse into wildcard name variants, and a row whose counter was
    deleted from source is flagged (a documented metric no scrape will
    ever return again)."""
    from tools import check_metric_names as cm

    rows = cm.readme_metric_rows()
    assert "rpc.server.qps" in rows                      # plain row
    assert "plog.append.group_size" in rows              # this PR's rows
    assert any(r.startswith("app.*") for r in rows)      # <holes> -> *
    monkeypatch.setattr(cm, "readme_metric_rows",
                        lambda: rows + ["ghost.deleted_counter_qps"])
    errs = cm.run_lint()
    assert any("ghost.deleted_counter_qps" in e for e in errs)


def test_fsck_clean_corrupt_and_orphan(tmp_path, capsys):
    """tools/fsck.py (ISSUE 17): the offline half of the integrity plane.
    Clean dir -> exit 0; a bit-flipped SST -> exit 1 with a typed
    `corrupt` finding; an orphan SST alone stays exit 0 (info, not rot);
    a MANIFEST reference to a missing file -> exit 1."""
    import glob
    import os
    import shutil

    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import SCHEMAS
    from pegasus_tpu.engine import EngineOptions, LsmEngine
    from tools.fsck import main as fsck_main

    d = str(tmp_path / "db")
    eng = LsmEngine(d, EngineOptions(backend="cpu"))
    for i in range(30):
        eng.put(generate_key(b"hk", b"sk%03d" % i),
                SCHEMAS[2].generate_value(0, 0, b"v%d" % i))
    eng.flush()
    eng.close()

    assert fsck_main([d]) == 0
    capsys.readouterr()

    ssts = sorted(glob.glob(os.path.join(d, "*.sst")))
    assert ssts
    # orphan: an unreferenced copy is waste, not rot -> still exit 0
    shutil.copy(ssts[0], os.path.join(d, "999999.sst"))
    assert fsck_main([d, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert any(f["kind"] == "orphan" and f["severity"] == "info"
               for f in out["findings"])

    # bit-flip -> error finding, exit 1, machine-readable shape
    size = os.path.getsize(ssts[0])
    with open(ssts[0], "r+b") as f:
        f.seek(size - 8)
        tail = f.read(8)
        f.seek(size - 8)
        f.write(bytes(b ^ 0xFF for b in tail))
    assert fsck_main([d, "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["errors"] >= 1
    assert any(f["kind"] == "corrupt" and f["path"] == ssts[0]
               for f in out["findings"])

    # walk mode: the node root finds the data dir below it; a missing
    # manifest reference is an error too
    os.remove(ssts[0])
    assert fsck_main([str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "manifest_missing" in err
    assert fsck_main(["/nonexistent/fsck/root"]) == 1
