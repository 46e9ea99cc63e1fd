"""Concurrency lint plane (ISSUE 9): tools/analyze + runtime/lockrank.

Every checker is proven against a SEEDED defect (a synthetic module it
must flag) and a clean twin it must pass — a lint that cannot catch its
own bug class is decoration. Plus: the repo-clean gate that wires the
whole plane into tier-1, the AB/BA lock-order detection (no unlucky
interleaving needed: the graph persists across threads), and the
grouped-onebox write workload under PEGASUS_LOCKRANK=1 proving the real
serving stack is cycle-free.
"""

import json
import os
import textwrap
import threading

import pytest

from tools.analyze import Repo, load_baseline, run_all, run_pass


# ---------------------------------------------------------------- helpers

def make_repo(tmp_path, modules: dict, readme: str = "") -> Repo:
    """A throwaway repo shaped like this one: modules land under
    pegasus_tpu/, README.md beside them."""
    (tmp_path / "pegasus_tpu").mkdir(exist_ok=True)
    for rel, src in modules.items():
        p = tmp_path / "pegasus_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    (tmp_path / "README.md").write_text(textwrap.dedent(readme))
    return Repo(tmp_path)


# ------------------------------------------------------- lock_discipline

GUARDED_BAD = """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._files = []  #: guarded_by self._lock

        def good(self):
            with self._lock:
                self._files.append(1)

        def bad(self):
            self._files.append(2)
"""


def test_lock_discipline_flags_guarded_write_outside_lock(tmp_path):
    repo = make_repo(tmp_path, {"m.py": GUARDED_BAD})
    findings = run_pass("lock_discipline", repo)
    assert len(findings) == 1
    f = findings[0]
    assert "Engine.bad" in f.message and "self._files" in f.message
    assert f.file == "pegasus_tpu/m.py"
    # the clean method produced nothing, and the key is line-stable
    assert "bad" in f.key and str(f.line) not in f.key


def test_lock_discipline_requires_and_escapes(tmp_path):
    repo = make_repo(tmp_path, {"m.py": """
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(self._lock)
            self._n = 0  #: guarded_by self._lock

        def locked_helper(self):  #: requires self._lock
            self._n += 1

        def via_condition(self):
            with self._cv:
                self._n += 1

        def reasoned_escape(self):
            return self._n  #: unguarded_ok racy gauge read

        def reasonless_escape(self):
            return self._n  #: unguarded_ok

        def closure_leak(self):
            with self._lock:
                def later():
                    self._n += 1
                return later
    """})
    findings = run_pass("lock_discipline", repo)
    msgs = [f.message for f in findings]
    # requires + condition alias + reasoned escape are all clean
    assert not any("locked_helper" in m or "via_condition" in m
                   or "reasoned_escape" in m for m in msgs)
    # an EMPTY unguarded_ok reason does not suppress
    assert any("reasonless_escape" in m for m in msgs)
    # a closure born under the lock runs AFTER it: inherits nothing
    assert any("closure_leak" in m for m in msgs)


def test_lock_discipline_module_level_guard(tmp_path):
    repo = make_repo(tmp_path, {"m.py": """
    import threading

    _POOL_LOCK = threading.Lock()
    _POOL = None  #: guarded_by _POOL_LOCK

    def good():
        global _POOL
        with _POOL_LOCK:
            if _POOL is None:
                _POOL = object()
            return _POOL

    def bad():
        return _POOL
    """})
    findings = run_pass("lock_discipline", repo)
    assert len(findings) == 1 and "bad" in findings[0].message


# ------------------------------------------------------ thread_lifecycle

def test_thread_lifecycle_flags_raw_spawn(tmp_path):
    repo = make_repo(tmp_path, {"m.py": """
    import threading
    from concurrent.futures import ThreadPoolExecutor

    def raw():
        threading.Thread(target=print, daemon=True).start()
        return ThreadPoolExecutor(2)

    def escaped():
        return threading.Thread(target=print)  #: untracked_ok test fixture thread joined by its caller

    class Factory(threading.Thread):
        pass
    """})
    findings = run_pass("thread_lifecycle", repo)
    msgs = [f.message for f in findings]
    assert sum("raw" in m for m in msgs) == 2  # Thread + executor
    assert not any("escaped" in m for m in msgs)
    assert any("Factory" in m and "subclasses" in m for m in msgs)


def test_spawn_helpers_register_in_tracked_registry():
    from pegasus_tpu.runtime.tasking import (TRACKED, spawn_thread,
                                             tracked_executor)

    ev = threading.Event()
    t = spawn_thread(ev.wait, 5.0, name="tracked-test")
    ex = tracked_executor(1, thread_name_prefix="tracked-test")
    try:
        assert t in TRACKED.live_threads()
        assert ex in TRACKED.live_executors()
    finally:
        ev.set()
        t.join(5)
        ex.shutdown(wait=False)


# ------------------------------------------------------------- env_knobs

KNOB_README = """
    ### Configuration-knob table

    | Knob | Default | Effect |
    |---|---|---|
    | `PEGASUS_DOCUMENTED` | 1 | a knob both read and documented |
    | `PEGASUS_GHOST` | 0 | a knob nothing reads any more |
"""


def test_env_knobs_both_directions(tmp_path):
    repo = make_repo(tmp_path, {"m.py": """
    import os

    def knobs():
        a = os.environ.get("PEGASUS_DOCUMENTED", "1")
        b = os.environ.get("PEGASUS_UNREGISTERED", "0")
        return a, b
    """}, readme=KNOB_README)
    keys = {f.key for f in run_pass("env_knobs", repo)}
    assert keys == {"undoc:PEGASUS_UNREGISTERED", "stale-row:PEGASUS_GHOST"}


def test_env_knobs_expands_prefix_families(tmp_path):
    repo = make_repo(tmp_path, {"m.py": """
    import os

    def _env_float(name, default):
        return float(os.environ.get(name, default))

    class Cfg:
        @classmethod
        def from_env(cls, env_prefix="PEGASUS_ALPHA"):
            return _env_float(f"{env_prefix}_TIMEOUT_S", 1.0)

    CFG_B = Cfg.from_env("PEGASUS_BETA")
    """}, readme=KNOB_README)
    from tools.analyze.env_knobs import source_knobs

    knobs = source_knobs(repo)
    assert {"PEGASUS_ALPHA_TIMEOUT_S", "PEGASUS_BETA_TIMEOUT_S"} <= knobs


def test_env_knobs_scanned_set_does_not_grow():
    """The distinct PEGASUS_* names the code reads: 81 after PR 32 took
    out the 42 that only the pre-ledger bench tools read (123 before).
    Every name is a configuration somebody must test; raising this
    number is a reviewed act, not a side effect."""
    from tools.analyze.env_knobs import source_knobs

    assert len(source_knobs(Repo())) <= 81


def test_env_knobs_ignores_docstring_mentions(tmp_path):
    repo = make_repo(tmp_path, {"m.py": '''
    """Docs may mention PEGASUS_FANTASY freely — docs are not reads."""

    def nothing():
        return 0
    '''}, readme=KNOB_README)
    keys = {f.key for f in run_pass("env_knobs", repo)}
    assert "undoc:PEGASUS_FANTASY" not in keys
    # both table rows are now stale (nothing reads them)
    assert "stale-row:PEGASUS_DOCUMENTED" in keys


# ---------------------------------------------------------------- events

EVENTS_OK = """
    from pegasus_tpu.runtime import events

    def trip():
        events.emit("lane.breaker_trip", severity="error", lane="compact")
"""

EVENT_README = """
    ### Event table

    | event | severity | transition it records |
    |---|---|---|
    | `lane.breaker_trip` | error | a breaker opened |
"""


def test_events_pass_clean_twin(tmp_path):
    repo = make_repo(tmp_path, {"m.py": EVENTS_OK}, readme=EVENT_README)
    assert run_pass("events", repo) == []


def test_events_pass_both_directions(tmp_path):
    # an emit with no table row, and a table row with no emit
    repo = make_repo(tmp_path, {"m.py": EVENTS_OK + """
    def ghost():
        events.emit("ghost.event", why="undocumented")
    """}, readme=EVENT_README + """
    | `stale.event` | info | deleted emitter, row kept |
    """)
    keys = {f.key for f in run_pass("events", repo)}
    assert "undoc:ghost.event" in keys
    assert "stale-row:stale.event" in keys
    assert not any(k.startswith(("undoc:lane.", "stale-row:lane."))
                   for k in keys)


def test_events_pass_requires_table(tmp_path):
    repo = make_repo(tmp_path, {"m.py": EVENTS_OK}, readme="# nothing")
    assert [f.key for f in run_pass("events", repo)] == ["no-table"]


def test_events_pass_flags_nonliteral_names(tmp_path):
    """A dynamic event name is invisible to the lint and to anyone
    grepping an incident artifact — flagged even if it happens to land
    on a documented name at runtime."""
    repo = make_repo(tmp_path, {"m.py": EVENTS_OK + """
    def dynamic(kind):
        events.emit(f"lane.{kind}", lane="compact")

    def indirect(name):
        events.emit(name, lane="compact")
    """}, readme=EVENT_README)
    nonlit = [f for f in run_pass("events", repo)
              if f.key.startswith("nonliteral:")]
    assert len(nonlit) == 2
    assert all("plain string literal" in f.message for f in nonlit)


# ------------------------------------------------------------ span_names

SPANS_OK = """
    from pegasus_tpu.runtime.job_trace import JOB_TRACER
    from pegasus_tpu.runtime.tracing import COMPACT_TRACER

    def work(job):
        with COMPACT_TRACER.span("pack", records=1):
            pass
        with JOB_TRACER.hop("engine.merge", where="local"):
            JOB_TRACER.note("sched.decide", gpid="1.0")
        self._trace(job, "offload.svc.merge", ms=3)
        with COMPACT_TRACER.span_in(None, "rpc.reply", bytes=0):
            pass
"""

SPAN_README = """
    ### Span-name table

    | span / hop | tracer | what it times |
    |---|---|---|
    | `pack` | stage | columnarization |
    | `engine.merge` / `sched.decide` | job | merge hop; the minting decision |
    | `offload.svc.merge` | job (service-side) | the remote merge |
    | `rpc.reply` | request | a span under a given context |
"""


def test_span_names_pass_clean_twin(tmp_path):
    repo = make_repo(tmp_path, {"m.py": SPANS_OK}, readme=SPAN_README)
    assert run_pass("span_names", repo) == []


def test_span_names_pass_both_directions(tmp_path):
    repo = make_repo(tmp_path, {"m.py": SPANS_OK + """
    def ghost():
        with JOB_TRACER.hop("ghost.hop"):
            pass
    """}, readme=SPAN_README + """
    | `stale.span` | stage | deleted call site, row kept |
    """)
    keys = {f.key for f in run_pass("span_names", repo)}
    assert "undoc:ghost.hop" in keys
    assert "stale-row:stale.span" in keys
    assert not any(k.endswith((":pack", ":engine.merge", ":sched.decide",
                               ":offload.svc.merge", ":rpc.reply"))
                   for k in keys)


def test_span_names_pass_requires_table(tmp_path):
    repo = make_repo(tmp_path, {"m.py": SPANS_OK}, readme="# nothing")
    assert [f.key for f in run_pass("span_names", repo)] == ["no-table"]


def test_span_names_pass_exempts_dynamic_names(tmp_path):
    """Unlike event names, span names are legitimately parameterized
    (client.<op>, rpc.<code>, the <kind>.nested degradation hop) —
    dynamic call sites are exempt, never flagged."""
    repo = make_repo(tmp_path, {"m.py": SPANS_OK + """
    def dynamic(op, kind):
        with COMPACT_TRACER.span(f"client.{op}"):
            pass
        with JOB_TRACER.hop(f"{kind}.nested"):
            pass
    """}, readme=SPAN_README)
    assert run_pass("span_names", repo) == []


# ------------------------------------------------------------- doc_paths

def test_doc_paths_clean_on_tree():
    """Every source file README.md names exists — and the pass really
    reads the README's three kinds of mention."""
    from tools.analyze.doc_paths import readme_paths

    assert run_pass("doc_paths", Repo()) == []
    named = readme_paths(Repo())
    assert {"tools/pressure_test.py", "ops/compact.py",
            "benchmarks/run.py"} <= set(named)


def test_doc_paths_flags_planted_missing_path(tmp_path):
    repo = make_repo(tmp_path, {"m.py": "X = 1\n",
                                "ops/merge.py": "Y = 2\n"}, readme="""
    The merge lives in `ops/merge.py` (`pegasus_tpu/m.py::X` configures
    it); `tools/check_*.py` and `<dir>/trace.py` are not paths.

    ```bash
    python ghost_entry.py --n 10
    python tools/ghost.py
    ```

    Gone too: `ops/ghost.py`, `pegasus_tpu/ghost/`.
    """)
    keys = [f.key for f in run_pass("doc_paths", repo)]
    assert keys == ["missing:ghost_entry.py", "missing:tools/ghost.py",
                    "missing:ops/ghost.py", "missing:pegasus_tpu/ghost/"]


# -------------------------------------------------------------- lockrank

def _graph():
    from pegasus_tpu.runtime import lockrank

    return lockrank._Graph()


def test_lockrank_detects_ab_ba_cycle(monkeypatch):
    """The classic inversion, WITHOUT needing the unlucky interleaving:
    the graph is process-wide and persists, so sequential A->B then
    B->A (even on one thread) is caught and names both sites."""
    monkeypatch.setenv("PEGASUS_LOCKRANK", "1")
    from pegasus_tpu.runtime import lockrank

    g = _graph()
    a = lockrank.named_lock("t.A", _graph=g)
    b = lockrank.named_rlock("t.B", _graph=g)
    with a:
        with b:
            pass
    assert g.snapshot()["violations"] == []
    with b:
        with a:
            pass
    (v,) = g.snapshot()["violations"]
    assert v["cycle"] == ["t.A", "t.B", "t.A"]
    assert "test_analyze.py" in v["acquire_site"]
    assert "test_analyze.py" in v["reverse_edge"]["acquire_site"]
    # reported once per edge pair, not per occurrence
    with b:
        with a:
            pass
    assert len(g.snapshot()["violations"]) == 1


def test_lockrank_longer_cycle_and_condition_wait(monkeypatch):
    monkeypatch.setenv("PEGASUS_LOCKRANK", "1")
    from pegasus_tpu.runtime import lockrank

    g = _graph()
    a = lockrank.named_lock("c.a", _graph=g)
    b = lockrank.named_lock("c.b", _graph=g)
    c = lockrank.named_lock("c.c", _graph=g)
    for first, second in ((a, b), (b, c), (c, a)):
        with first:
            with second:
                pass
    (v,) = g.snapshot()["violations"]
    assert v["cycle"] == ["c.a", "c.b", "c.c", "c.a"]

    # Condition.wait releases the lock: a waiter holding the condition
    # must NOT generate held-while-acquiring edges for locks the waker
    # takes, and the held-stack drains clean
    g2 = _graph()
    cv = lockrank.named_condition("c.cv", _graph=g2)
    other = lockrank.named_lock("c.other", _graph=g2)
    woke = threading.Event()

    def waiter():
        with cv:
            cv.wait(5.0)
        woke.set()

    t = threading.Thread(target=waiter)
    t.start()
    import time

    time.sleep(0.05)
    with other:
        with cv:
            cv.notify_all()
    t.join(5)
    assert woke.is_set()
    assert g2.snapshot()["violations"] == []
    assert lockrank._held() == []


def test_lockrank_disabled_returns_raw_primitives(monkeypatch):
    monkeypatch.setenv("PEGASUS_LOCKRANK", "0")
    from pegasus_tpu.runtime import lockrank

    assert type(lockrank.named_lock("x")) is type(threading.Lock())
    cv = lockrank.named_condition("x")
    assert isinstance(cv, threading.Condition)


def test_lockrank_raise_mode(monkeypatch):
    monkeypatch.setenv("PEGASUS_LOCKRANK", "raise")
    from pegasus_tpu.runtime import lockrank

    g = _graph()
    a = lockrank.named_lock("r.a", _graph=g)
    b = lockrank.named_lock("r.b", _graph=g)
    with a:
        with b:
            pass
    with pytest.raises(lockrank.LockOrderError):
        with b:
            with a:
                pass
    # the failed acquire still HOLDS b+a; reset this thread's stack so
    # the shared per-thread state can't leak into later tests
    lockrank._held().clear()


def test_lockrank_grouped_onebox_write_workload(tmp_path):
    """Acceptance: a grouped-onebox write workload (parent router +
    group-worker subprocesses, all under the session's
    PEGASUS_LOCKRANK=1) records ZERO lock-order cycles — in this
    process' graph and in the shared violation file the workers
    inherit."""
    from pegasus_tpu.runtime import lockrank
    from tests.test_satellites import MiniCluster

    assert lockrank.enabled(), "conftest must arm PEGASUS_LOCKRANK"
    sink = os.environ["PEGASUS_LOCKRANK_FILE"]

    def sink_lines():
        try:
            with open(sink) as f:
                return [line for line in f if line.strip()]
        except OSError:
            return []

    before_g = len(lockrank.GRAPH.violations)
    before_f = len(sink_lines())
    c = MiniCluster(tmp_path, n_nodes=2, serve_groups=2)
    try:
        cli = c.create("lockrank_t", partitions=4, replicas=2)
        try:
            for i in range(120):
                cli.set(b"lk%d" % i, b"s", b"v%d" % i)
            for i in range(0, 120, 3):
                cli.delete(b"lk%d" % i, b"s")
            for i in range(1, 120, 3):
                assert cli.get(b"lk%d" % i, b"s") == b"v%d" % i
        finally:
            cli.close()
    finally:
        c.stop()
    assert len(lockrank.GRAPH.violations) == before_g, \
        lockrank.GRAPH.violations[before_g:]
    assert len(sink_lines()) == before_f, sink_lines()[before_f:]


# ------------------------------------------- lock-discipline fix regress

def test_set_read_residency_holds_engine_lock(tmp_path):
    """Regression for the unlocked _read_hot flip the lock-discipline
    pass caught (now written under the engine lock), AND for the review
    bug the fix briefly introduced: a duplicated nested prime loop that
    submitted N + N*N prime jobs for N SSTs. With a tpu backend the pin
    must submit EXACTLY one prime per current SST."""
    from pegasus_tpu.engine.db import EngineOptions, LsmEngine

    eng = LsmEngine(str(tmp_path / "e"), EngineOptions(backend="cpu"))
    try:
        eng.set_read_residency(True)
        assert eng.stats()["read_hot"] is True
        eng.set_read_residency(False)
        assert eng.stats()["read_hot"] is False
    finally:
        eng.close()

    eng = LsmEngine(str(tmp_path / "t"),
                    EngineOptions(backend="tpu", memtable_bytes=1))
    try:
        for i in range(3):
            eng.put(b"k%d" % i, b"v")
            eng.flush()
        n_ssts = eng.stats()["l0_files"] + sum(
            eng.stats()["level_files"].values())
        assert n_ssts >= 2
        primed = []
        eng._prime_async = primed.append
        eng.set_read_residency(True)
        assert len(primed) == n_ssts, "one prime submission per SST"
    finally:
        eng._prime_async = lambda sst: None  # close() must not re-prime
        eng.close()


def test_flush_trigger_compacts_outside_flush_lock(tmp_path):
    """Regression for the lock-order cycle lockrank caught on the LIVE
    suite: the L0 compaction trigger used to run under the flush lock
    (flush->compaction), while batched_manual_compact flushes engine
    i+1 holding engine i's compaction lock (compaction->flush) — a
    deadlock waiting for the interleaving. The trigger now fires after
    the flush lock is released: exercising the exact path must leave NO
    flush->compaction edge in the process-wide graph."""
    from pegasus_tpu.engine.db import EngineOptions, LsmEngine
    from pegasus_tpu.runtime import lockrank

    assert lockrank.enabled()
    eng = LsmEngine(str(tmp_path / "e"),
                    EngineOptions(backend="cpu", l0_compaction_trigger=1,
                                  memtable_bytes=1))
    try:
        for i in range(3):
            eng.put(b"k%d" % i, b"v")  # rotate -> drain -> trigger
        eng.flush()
    finally:
        eng.close()
    with lockrank.GRAPH._mu:
        assert "engine.compaction" not in \
            lockrank.GRAPH.edges.get("engine.flush", {})


def test_manual_compact_finish_time_written_under_lock(tmp_path):
    """Regression for the unlocked _meta write in manual_compact: the
    finish timestamp still lands (and the manifest persists it) with the
    write now inside the engine lock."""
    from pegasus_tpu.engine.db import (META_LAST_MANUAL_COMPACT_FINISH_TIME,
                                       EngineOptions, LsmEngine)

    eng = LsmEngine(str(tmp_path / "e"), EngineOptions(backend="cpu"))
    try:
        eng.put(b"k1", b"v1")
        eng.manual_compact()
        ts = int(eng.meta_store[META_LAST_MANUAL_COMPACT_FINISH_TIME])
        assert ts > 0
    finally:
        eng.close()


# ------------------------------------------------------------ the runner

def test_runner_baseline_semantics(tmp_path):
    repo = make_repo(tmp_path, {"m.py": GUARDED_BAD})
    # no baseline: the seeded finding fails the run
    r = run_all(repo, passes=["lock_discipline"], baseline={})
    assert not r.clean and len(r.findings) == 1
    key = r.findings[0].key
    # baselined: tracked as grandfathered, run is clean
    r = run_all(repo, passes=["lock_discipline"],
                baseline={"lock_discipline": {key}})
    assert r.clean and len(r.grandfathered) == 1 and not r.findings
    # stale entry (finding gone, entry kept) fails — debt must shrink
    r = run_all(repo, passes=["lock_discipline"],
                baseline={"lock_discipline": {key, "ghost:key"}})
    assert not r.clean
    assert ("lock_discipline", "ghost:key") in r.stale_baseline


def test_analyze_cli_json():
    import subprocess
    import sys
    from pathlib import Path

    repo_root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--json",
         "--pass", "lock_discipline", "--pass", "thread_lifecycle"],
        capture_output=True, text=True, timeout=120, cwd=repo_root)
    doc = json.loads(proc.stdout)
    assert doc["clean"] is True and proc.returncode == 0
    assert set(doc["passes"]) == {"lock_discipline", "thread_lifecycle"}


def test_repo_clean():
    """THE tier-1 gate: every pass of the static-analysis plane is clean
    against this repository, modulo the committed baseline (which must
    itself be exact — stale entries fail). A new unguarded access, raw
    thread spawn, undocumented knob/counter/command/fail-point, or
    deleted-but-still-documented surface fails tier-1 here."""
    report = run_all(Repo(), baseline=load_baseline())
    lines = [f.render() for f in report.findings] + [
        f"STALE baseline: {p}:{k}" for p, k in report.stale_baseline]
    assert report.clean, "\n".join(lines)
    assert set(report.ran) == {"doc_paths", "env_knobs", "events",
                               "fail_points",
                               "lock_discipline", "metric_names",
                               "remote_commands", "span_names",
                               "thread_lifecycle"}
