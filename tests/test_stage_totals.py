"""The stage spine (ISSUE 27): every span either tracer closes adds to
`stage.<name>.{n,us,self_us}`, self time is duration minus the spans
closed inside it in the same thread, waits are spans of their own, request
traces can be switched off without stopping the totals, the slow ledger
keeps the worst traces for good, and an open span holds a profiler
annotation only in a process that has loaded jax.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from pegasus_tpu.replication.mutation_log import LogMutation, MutationLog
from pegasus_tpu.rpc.transport import RpcConnection, RpcServer
from pegasus_tpu.runtime import fail_points as fp
from pegasus_tpu.runtime import tracing
from pegasus_tpu.runtime.perf_counters import counters
from pegasus_tpu.runtime.remote_command import RemoteCommandService
from pegasus_tpu.runtime.tracing import (COMPACT_TRACER, REQUEST_TRACER,
                                         RequestTracer, StageTracer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def totals(name: str) -> dict:
    return {k: counters.number(f"stage.{name}.{k}").value()
            for k in ("n", "us", "self_us")}


def moved(name: str, before: dict) -> dict:
    after = totals(name)
    return {k: after[k] - before[k] for k in after}


def test_self_time_is_duration_minus_same_thread_children_across_tracers():
    st, rt = StageTracer(prefix="t27a"), RequestTracer()
    b_out, b_in = totals("t27.outer"), totals("t27.inner")
    with rt.span("t27.outer"):          # a request span ...
        time.sleep(0.02)
        with st.span("t27.inner"):      # ... around a stage span
            time.sleep(0.03)
    out, inn = moved("t27.outer", b_out), moved("t27.inner", b_in)
    assert out["n"] == inn["n"] == 1
    assert inn["us"] >= 30_000 and inn["self_us"] == inn["us"]
    assert out["us"] >= inn["us"] + 20_000
    assert out["self_us"] == out["us"] - inn["us"]


def test_a_span_closed_on_a_worker_thread_does_not_subtract():
    st = StageTracer(prefix="t27b")
    b = totals("t27.caller")
    with st.span("t27.caller"):
        sessions = st.propagate_sessions()

        def worker():
            st.adopt_sessions(sessions)
            with st.span("t27.worker"):
                time.sleep(0.03)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    got = moved("t27.caller", b)
    assert got["us"] >= 30_000
    assert got["self_us"] == got["us"]   # the caller's self time IS the wait


def test_request_span_without_a_context_totals_and_records_no_trace():
    tr = RequestTracer()
    b = totals("t27.orphan")
    with tr.span("t27.orphan", records=2) as attrs:
        attrs["records"] = 3
    assert moved("t27.orphan", b)["n"] == 1
    assert tr.trace() == [] and tr.slow_requests() == []


def test_event_is_all_self_time_and_subtracts_from_nothing():
    tr = RequestTracer()
    b_ev, b_par = totals("t27.event"), totals("t27.parent")
    with tr.root("OP"):
        with tr.span("t27.parent"):
            tr.event("t27.event", 7_000, batch=2)
    ev, par = moved("t27.event", b_ev), moved("t27.parent", b_par)
    assert ev == {"n": 1, "us": 7_000, "self_us": 7_000}
    assert par["self_us"] == par["us"]
    (trace,) = tr.trace(1)
    rec = next(s for s in trace["spans"] if s["name"] == "t27.event")
    assert rec["duration_us"] == 7_000 and rec["parent"] == "t27.parent"
    assert rec["batch"] == 2


def test_span_records_name_their_parent():
    tr = RequestTracer()
    tr.slow_threshold_us = 1 << 60
    with tr.root("OP"):
        with tr.span("t27.a"):
            with tr.span("t27.b"):
                pass
    (trace,) = tr.trace(1)
    parents = {s["name"]: s["parent"] for s in trace["spans"]}
    assert parents == {"t27.b": "t27.a", "t27.a": "client.OP",
                       "client.OP": ""}


def test_a_wait_is_one_span_however_often_the_loop_turns():
    tr = RequestTracer()
    b = totals("t27.wait")
    wait = tr.span("t27.wait")
    wait.end()                      # never parked: nothing closes
    assert moved("t27.wait", b)["n"] == 0
    for _ in range(3):
        wait.begin()
        time.sleep(0.005)
    wait.end()
    wait.end()
    got = moved("t27.wait", b)
    assert got["n"] == 1 and got["us"] >= 15_000


def test_stage_tracer_keeps_its_compact_stage_counters():
    st = StageTracer(prefix="t27c")
    with st.span("t27.exported", records=5, nbytes=9):
        pass
    st.event("t27.exported", 0.001)
    assert counters.rate("t27c.stage.t27.exported.count").total() == 2
    assert counters.rate("t27c.stage.t27.exported.records").total() == 5
    assert counters.rate("t27c.stage.t27.exported.bytes").total() == 9
    assert counters.percentile(
        "t27c.stage.t27.exported.duration_us").percentiles()["p50"] >= 0


@pytest.fixture
def echo_server():
    seen = []
    srv = RpcServer()
    srv.register("RPC_T27_ECHO", lambda h, b: (seen.append(h.trace_id), b)[1])
    srv.start()
    conn = RpcConnection(srv.address)
    yield conn, seen
    conn.close()
    srv.stop()


@pytest.mark.parametrize("every,traced", [(0, False), (1, True)])
def test_sample_every_zero_means_no_id_on_the_wire_and_totals_still_move(
        echo_server, monkeypatch, every, traced):
    conn, seen = echo_server
    monkeypatch.setattr(REQUEST_TRACER, "sample_every", every)
    names = ("rpc.server.RPC_T27_ECHO", "rpc.queue", "rpc.reply",
             "client.T27", "rpc.RPC_T27_ECHO")
    before = {n: totals(n) for n in names}
    done_before = counters.rate("request.trace.completed_count").total()
    with REQUEST_TRACER.root("T27") as ctx:
        assert (ctx is not None) == traced
        conn.call("RPC_T27_ECHO", b"x")
    assert bool(seen[-1]) == traced
    # the reply span closes after the response is on the wire
    deadline = time.monotonic() + 5
    while (moved("rpc.reply", before["rpc.reply"])["n"] < 1
           and time.monotonic() < deadline):
        time.sleep(0.01)
    for n in names:
        assert moved(n, before[n])["n"] == 1, n
    completed = counters.rate("request.trace.completed_count").total()
    assert completed - done_before == (1 if traced else 0)


def test_sample_every_reads_zero_from_the_environment(monkeypatch):
    monkeypatch.setenv("PEGASUS_TRACE_SAMPLE_EVERY", "0")
    assert RequestTracer().sample_every == 0
    monkeypatch.setenv("PEGASUS_TRACE_SAMPLE_EVERY", "-3")
    assert RequestTracer().sample_every == 0
    monkeypatch.delenv("PEGASUS_TRACE_SAMPLE_EVERY")
    assert RequestTracer().sample_every == 1


def test_every_counter_under_the_stage_prefix_is_a_scalar():
    with COMPACT_TRACER.span("t27.scalar"):
        pass
    with REQUEST_TRACER.span("t27.scalar.req"):
        pass
    svc = RemoteCommandService()
    svc.register_defaults("test")
    snap = json.loads(svc._commands["perf-counters-by-prefix"](["stage."]))
    assert "stage.t27.scalar.n" in snap and "stage.t27.scalar.req.us" in snap
    assert all(isinstance(v, int) for v in snap.values()), \
        {k: v for k, v in snap.items() if not isinstance(v, int)}
    assert {k.rsplit(".", 1)[1] for k in snap} == {"n", "us", "self_us"}


def test_plog_flush_total_is_the_flush_count(tmp_path):
    before = {n: totals(n) for n in ("plog.flush", "plog.append",
                                     "plog.group_wait")}
    log = MutationLog(str(tmp_path / "plog"), group_us=2000)
    decree = [0]
    lock = threading.Lock()

    def mutation():
        with lock:
            decree[0] += 1
            return LogMutation(decree=decree[0], ballot=1, codes=["c"],
                               bodies=[b"x" * 64])

    for _ in range(5):                       # solo appends
        log.append(mutation())
    fp.setup()
    try:
        # every leader dawdles between claim and flush, so the seven
        # other appenders are sure to queue behind it
        fp.cfg("plog.group", "sleep(3)")
        threads = [threading.Thread(
            target=lambda: [log.append(mutation()) for _ in range(25)])
            for _ in range(8)]               # grouped appends
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        fp.teardown()
    log.append_window([mutation(), mutation()])
    flushes = moved("plog.flush", before["plog.flush"])["n"]
    appends = moved("plog.append", before["plog.append"])["n"]
    assert flushes == log.flush_count
    assert appends == 5 + 8 * 25 + 1
    assert log.append_count == 5 + 8 * 25 + 2
    assert flushes < appends, "no group ever formed"
    waits = moved("plog.group_wait", before["plog.group_wait"])
    assert 0 < waits["n"] <= appends     # followers waited, each at most once


def test_a_degrade_behind_a_wedged_leader_is_a_named_wait_and_a_count(
        tmp_path):
    before = {n: totals(n) for n in ("plog.group_fallback",
                                     "plog.group_wait", "plog.flush")}
    log = MutationLog(str(tmp_path / "plog"))
    log._stall_s = 0.2
    fp.setup()
    try:
        fp.cfg("plog.group", "1*sleep(1500)")

        def append(d):
            log.append(LogMutation(decree=d, codes=["c"], bodies=[b"x"]))

        wedged = threading.Thread(target=append, args=(1,))
        wedged.start()
        time.sleep(0.1)      # the leader claimed decree 1 and now sleeps
        follower = threading.Thread(target=append, args=(2,))
        follower.start()
        follower.join()
        wedged.join()
    finally:
        fp.teardown()
    assert moved("plog.group_fallback", before["plog.group_fallback"])["n"] == 1
    wait = moved("plog.group_wait", before["plog.group_wait"])
    assert wait["n"] == 1 and wait["us"] >= 200_000   # the stall bound
    assert moved("plog.flush", before["plog.flush"])["n"] == log.flush_count


def test_slow_ledger_keeps_the_worst_traces_for_good():
    tr = RequestTracer(capacity=4, slow_capacity=4)
    tr.slow_threshold_us = 0
    durs = list(range(1, 101))
    for d in durs:
        e = tr._entry(d, "OP", root_local=True)
        tr._finalize(e, d * 1000, sampled=True)
    got = tr.slow_requests(4)
    worst = [t["duration_us"] for t in got[:tr.WORST]]
    assert worst == [d * 1000 for d in sorted(durs, reverse=True)[:tr.WORST]]
    # then the newest of the rest, none twice
    assert len({t["trace_id"] for t in got}) == len(got)
    assert tr.find(format(100, "016x")) is not None      # worst of all
    assert tr.find(format(69, "016x")) is not None       # 32nd worst
    assert tr.find(format(1, "016x")) is None            # fell off both


def test_spans_never_import_jax_in_a_process_without_it():
    code = (
        "import sys\n"
        "from pegasus_tpu.runtime.tracing import COMPACT_TRACER, "
        "REQUEST_TRACER\n"
        "with REQUEST_TRACER.root('OP'):\n"
        "    with REQUEST_TRACER.span('a'):\n"
        "        with COMPACT_TRACER.span('b'):\n"
        "            pass\n"
        "REQUEST_TRACER.event('c', 5)\n"
        "from pegasus_tpu.runtime.perf_counters import counters\n"
        "assert counters.number('stage.b.n').value() == 1\n"
        "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_an_open_span_holds_a_pegasus_annotation_when_jax_is_loaded(
        monkeypatch):
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))

        def __exit__(self, *exc):
            log.append(("close", self.name))

    monkeypatch.setattr(tracing, "_ANNOTATION", Annotation)
    # stage spans always; request spans only between profile-start and
    # profile-stop (annotate_requests), or they swell every other trace
    with REQUEST_TRACER.span("t27.ann.req"):
        with COMPACT_TRACER.span("t27.ann.stage"):
            pass
    assert log == [("open", "pegasus:t27.ann.stage"),
                   ("close", "pegasus:t27.ann.stage")]
    del log[:]
    tracing.annotate_requests(True)
    try:
        with REQUEST_TRACER.span("t27.ann.req"):
            with COMPACT_TRACER.span("t27.ann.stage"):
                pass
    finally:
        tracing.annotate_requests(False)
    assert log == [("open", "pegasus:t27.ann.req"),
                   ("open", "pegasus:t27.ann.stage"),
                   ("close", "pegasus:t27.ann.stage"),
                   ("close", "pegasus:t27.ann.req")]


def test_the_annotation_class_comes_from_sys_modules(monkeypatch):
    import types

    monkeypatch.setattr(tracing, "_ANNOTATION", None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert tracing._find_annotation() is None
    assert tracing._ANNOTATION is None

    class Fake:
        pass

    fake = types.ModuleType("jax")
    fake.profiler = types.SimpleNamespace(TraceAnnotation=Fake)
    monkeypatch.setitem(sys.modules, "jax", fake)
    assert tracing._find_annotation() is Fake
    assert tracing._ANNOTATION is Fake


def test_profile_commands_answer_without_jax_and_with_bad_arguments(
        monkeypatch):
    svc = RemoteCommandService()
    svc.register_defaults("test")
    assert svc._commands["profile-start"]([]).startswith("usage:")
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert svc._commands["profile-start"](["/tmp/x"]).startswith("no profile")
    assert svc._commands["profile-stop"]([]).startswith("no profile")


def test_profile_start_and_stop_write_a_trace_with_the_programs_spans(
        tmp_path):
    import glob

    import jax.numpy as jnp

    svc = RemoteCommandService()
    svc.register_defaults("test")
    out = svc._commands["profile-start"]([str(tmp_path)])
    assert out.startswith("profiling into"), out
    try:
        assert "failed" in svc._commands["profile-start"]([str(tmp_path)])
        with COMPACT_TRACER.span("t27.profiled"), \
                REQUEST_TRACER.span("t27.profiled.req"):
            jnp.arange(8).sum().block_until_ready()
    finally:
        assert svc._commands["profile-stop"]([]) == "profile written"
    assert tracing._ANNOTATE_REQUESTS is False
    assert "failed" in svc._commands["profile-stop"]([])
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert files
    with open(files[0], "rb") as f:
        raw = f.read()
    assert b"pegasus:t27.profiled" in raw
    assert b"pegasus:t27.profiled.req" in raw
