"""End-to-end request tracing tests: one client write through a real
onebox (meta + replicas over TCP, PacificA 2PC) must yield ONE trace
whose spans cover client, rpc, replication prepare/commit, the
private-log append and the engine apply — retrievable via
GET /requests/trace and the slow-requests remote command — plus the
RequestTracer unit surface and the new replication-path counters.
"""

import io
import json
import threading
import time
import urllib.request

import pytest

from pegasus_tpu.client import MetaResolver, PegasusClient
from pegasus_tpu.runtime.config import Config
from pegasus_tpu.runtime.perf_counters import counters
from pegasus_tpu.runtime.service_app import ServiceAppContainer
from pegasus_tpu.runtime.tracing import REQUEST_TRACER, RequestTracer, TraceContext
from pegasus_tpu.rpc.task_codes import RPC_PUT
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
http_port = 0
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
    root = tmp_path_factory.mktemp("tracebox")
    c1 = ServiceAppContainer(Config(
        text=ONEBOX_INI, variables={"root": str(root), "meta": "x"}))
    c1.start(only=["meta"])
    meta_addr = c1.apps["meta"].address
    c2 = ServiceAppContainer(Config(
        text=ONEBOX_INI, variables={"root": str(root), "meta": meta_addr}))
    c2.start(only=["replica1", "replica2", "replica3"])
    time.sleep(0.3)  # beacons land
    sh = Shell([meta_addr], out=io.StringIO())
    sh.run_line("create tracetest -p 2 -r 3")
    client = PegasusClient(MetaResolver([meta_addr], "tracetest"))
    yield meta_addr, c2.apps["replica1"], client
    client.close()
    c2.stop()
    c1.stop()


def _put_traces(traces):
    """Completed traces of replicated client puts (prepare span seen)."""
    return [t for t in traces
            if t["op"] == RPC_PUT
            and any(s["name"] == "replica.prepare" for s in t["spans"])]


def test_one_put_yields_one_trace_with_full_stage_timeline(onebox):
    """Acceptance: a single traced client write produces a single trace
    (one trace_id) holding >= 5 stage spans across client, rpc,
    replication (prepare/commit), mutation-log append and engine apply."""
    _, _, client = onebox
    before = {t["trace_id"] for t in _put_traces(REQUEST_TRACER.trace(500))}
    client.set(b"tk", b"sk", b"payload")
    new = [t for t in _put_traces(REQUEST_TRACER.trace(500))
           if t["trace_id"] not in before]
    assert len(new) == 1, "one client put must yield exactly one trace"
    trace = new[0]
    names = [s["name"] for s in trace["spans"]]
    assert len(names) >= 5
    assert any(n.startswith("client.") for n in names)
    assert any(n.startswith("rpc.") for n in names)
    assert "replica.prepare" in names
    assert "replica.commit" in names
    assert "plog.append" in names
    assert "engine.apply" in names
    # the waits and the work between those spans are named too (ISSUE 27)
    assert "rpc.queue" in names and "plog.flush" in names
    flush = next(s for s in trace["spans"] if s["name"] == "plog.flush")
    assert flush["parent"] == "plog.append"
    # span durations nest sanely: the client span covers the whole trace
    client_span = next(s for s in trace["spans"]
                       if s["name"].startswith("client."))
    assert client_span["duration_us"] <= trace["duration_us"]
    assert all(s["duration_us"] >= 0 for s in trace["spans"])


def test_one_get_yields_a_trace_through_the_engine_read(onebox):
    _, _, client = onebox
    client.set(b"tk", b"sk_get", b"payload")
    assert client.get(b"tk", b"sk_get") == b"payload"
    gets = [t for t in REQUEST_TRACER.trace(500)
            if t["op"].endswith("_GET")
            and any(s["name"] == "engine.get" for s in t["spans"])]
    assert gets
    by_name = {s["name"]: s for s in gets[-1]["spans"]}
    assert by_name["engine.get"]["parent"].startswith("rpc.server.")
    assert "rpc.queue" in by_name


def test_requests_trace_http_route_serves_the_trace(onebox):
    _, rep_app, client = onebox
    client.set(b"hk", b"sk", b"http-surface")
    host, port = rep_app.reporter.address
    body = json.loads(urllib.request.urlopen(
        f"http://{host}:{port}/requests/trace?last=500", timeout=5).read())
    puts = _put_traces(body["traces"])
    assert puts, "PUT trace must be retrievable via GET /requests/trace"
    # ?id= fetches one trace by hex id
    tid = puts[-1]["trace_id"]
    one = json.loads(urllib.request.urlopen(
        f"http://{host}:{port}/requests/trace?id={tid}", timeout=5).read())
    assert one["trace"] is not None and one["trace"]["trace_id"] == tid


def test_slow_request_ledger_and_remote_command(onebox):
    """Any request over the threshold keeps its full stage timeline in
    the ledger regardless of sampling, served by `slow-requests`."""
    from pegasus_tpu.rpc import codec
    from pegasus_tpu.rpc.transport import RpcConnection
    from pegasus_tpu.runtime.remote_command import (RemoteCommandRequest,
                                                    RemoteCommandResponse)

    meta_addr, rep_app, client = onebox
    old = REQUEST_TRACER.slow_threshold_us
    REQUEST_TRACER.slow_threshold_us = 0  # everything is "slow"
    try:
        client.set(b"slowk", b"sk", b"ledger-me")
    finally:
        REQUEST_TRACER.slow_threshold_us = old
    ledger = REQUEST_TRACER.slow_requests(500)
    slow_puts = _put_traces(ledger)
    assert slow_puts, "the put must land in the slow-request ledger"
    assert any(s["name"] == "plog.append" for s in slow_puts[-1]["spans"])

    host, _, port = rep_app.address.rpartition(":")
    conn = RpcConnection((host, int(port)))
    try:
        _, body = conn.call("RPC_CLI_CLI_CALL", codec.encode(
            RemoteCommandRequest("slow-requests", ["500"])), timeout=10)
        out = json.loads(codec.decode(RemoteCommandResponse, body).output)
    finally:
        conn.close()
    assert any(t["trace_id"] == slow_puts[-1]["trace_id"] for t in out)
    # the http twin of the ledger
    hhost, hport = rep_app.reporter.address
    body = json.loads(urllib.request.urlopen(
        f"http://{hhost}:{hport}/requests/trace?slow=1&last=500",
        timeout=5).read())
    assert _put_traces(body["slow_requests"])


def test_metrics_route_serves_replication_counters(onebox):
    """Acceptance: /metrics covers the write path — replica.* and plog.*
    counters appear after a replicated write (percentiles flattened to
    _p50.._p999 series)."""
    _, rep_app, client = onebox
    client.set(b"mk", b"sk", b"metrics")
    host, port = rep_app.reporter.address
    body = urllib.request.urlopen(
        f"http://{host}:{port}/metrics", timeout=5).read().decode()
    assert "replica_prepare_latency_us_p99" in body
    assert "replica_commit_latency_us_p50" in body
    assert "plog_append_count" in body
    assert "plog_append_duration_us_p999" in body
    assert "rpc_server_latency_us_p99" in body
    # write-path latency parity: puts now have a percentile counter
    assert "put_latency_us_p99" in body


def test_write_latency_parity_counters(onebox):
    _, _, client = onebox
    client.multi_set(b"wl", {b"a": b"1", b"b": b"2"})
    client.incr(b"wl", b"n", 3)
    client.delete(b"wl", b"a")
    snap = counters.snapshot(substr="_latency_us")
    for op in ("multi_put", "incr", "remove"):
        keys = [k for k in snap if k.endswith(f".{op}_latency_us")]
        assert keys, f"missing {op}_latency_us percentile counter"
        assert all(isinstance(snap[k], dict)
                   and set(snap[k]) == {"p50", "p90", "p95", "p99", "p999"}
                   for k in keys)


def test_per_partition_write_gauges(onebox):
    _, _, client = onebox
    client.set(b"gk", b"sk", b"gauge")
    snap = counters.snapshot(prefix="replica.")
    assert any(k.endswith(".inflight") for k in snap)
    assert any(k.endswith(".backlog") for k in snap)
    # the write committed: its partition's backlog drained back to 0
    hot = [k for k in snap if k.endswith(".backlog")]
    assert all(snap[k] == 0 for k in hot)


# ------------------------------------------------------- tracer unit tests


def test_request_tracer_root_and_span_nesting():
    tr = RequestTracer()
    tr.slow_threshold_us = 1 << 60
    with tr.root("OP") as ctx:
        assert tr.current() is ctx
        with tr.span("stage.a", records=3):
            with tr.span("stage.b"):
                pass
    assert tr.current() is None
    (trace,) = tr.trace(1)
    assert trace["op"] == "OP"
    names = [(s["name"], s["depth"]) for s in trace["spans"]]
    # close order: children before parents; client.<op> is the root span
    assert names == [("stage.b", 2), ("stage.a", 1), ("client.OP", 0)]
    assert trace["spans"][1]["records"] == 3


def test_request_tracer_spans_without_context_record_no_trace():
    """No context, no per-trace record — the span is still timed into the
    stage totals (tests/test_stage_totals.py)."""
    tr = RequestTracer()
    before = counters.number("stage.orphan.n").value()
    with tr.span("orphan"):
        pass
    assert tr.trace() == []
    assert tr.slow_requests() == []
    assert counters.number("stage.orphan.n").value() == before + 1


def test_request_tracer_serve_finalizes_remote_view():
    """A wire-propagated context with no local root finalizes once the
    last open handler returns (the peer node's partial trace view)."""
    tr = RequestTracer()
    tr.slow_threshold_us = 1 << 60
    ctx = TraceContext(0xABC, sampled=True, remote=True)
    with tr.serve(ctx, "RPC_X"):
        with tr.span("replica.on_prepare"):
            pass
    (trace,) = tr.trace(1)
    assert trace["trace_id"] == format(0xABC, "016x")
    assert [s["name"] for s in trace["spans"]] == \
        ["replica.on_prepare", "rpc.server.RPC_X"]


def test_request_tracer_sampling_and_ledger_are_independent():
    tr = RequestTracer()
    tr.sample_every = 1 << 30   # effectively never sampled
    tr.slow_threshold_us = 0    # everything is slow
    with tr.root("OP"):
        pass
    assert tr.trace() == []                 # not sampled
    assert len(tr.slow_requests()) == 1     # but ledgered
    assert tr.find(tr.slow_requests()[0]["trace_id"]) is not None


def test_parallel_prepare_keeps_spans_in_the_trace(tmp_path, monkeypatch):
    """PEGASUS_PARALLEL_PREPARE=1 fans prepares out on a worker pool; the
    thread-local trace context must survive the hop or the secondaries'
    spans (and the trace_id on the wire) silently vanish."""
    from pegasus_tpu.base import key_schema
    from pegasus_tpu.replication import ReplicaGroup
    from pegasus_tpu.rpc import messages as msg

    monkeypatch.setenv("PEGASUS_PARALLEL_PREPARE", "1")
    g = ReplicaGroup(str(tmp_path), n=3)
    try:
        tr = RequestTracer()
        tr.slow_threshold_us = 1 << 60
        key = key_schema.generate_key(b"ph", b"ps")
        # patch the process tracer the replication layer uses
        import pegasus_tpu.replication.mutation_log as ml
        import pegasus_tpu.replication.replica as rp

        monkeypatch.setattr(rp, "REQUEST_TRACER", tr)
        monkeypatch.setattr(ml, "REQUEST_TRACER", tr)
        with tr.root("PUT"):
            g.write(RPC_PUT, msg.UpdateRequest(key, b"v", 0))
        (trace,) = tr.trace(1)
        names = [s["name"] for s in trace["spans"]]
        # primary append + BOTH secondaries' pool-thread appends join it
        assert names.count("plog.append") == 3, names
        assert names.count("replica.on_prepare") == 2, names
    finally:
        g.close()


def test_request_tracer_cross_thread_spans_join_the_trace():
    """Spans closed by another thread holding the same context land in
    the same trace (the onebox server-side shape)."""
    tr = RequestTracer()
    tr.slow_threshold_us = 1 << 60
    done = threading.Event()

    with tr.root("OP") as ctx:
        def server():
            with tr.serve(TraceContext(ctx.trace_id, True, remote=True),
                          "RPC_X"):
                with tr.span("plog.append"):
                    pass
            done.set()

        t = threading.Thread(target=server)
        t.start()
        assert done.wait(5)
        t.join()
    (trace,) = tr.trace(1)
    names = {s["name"] for s in trace["spans"]}
    assert {"client.OP", "rpc.server.RPC_X", "plog.append"} <= names
