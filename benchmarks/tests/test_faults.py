"""The rest of a run with the timed path broken underneath: `correct` has
to come out false. The harness's look for a chip is skipped (--rehearsal);
everything else is the run as the driver makes it.

Faults each cell can have: a step that returns its state unchanged, half
of the batch left out, an answer altered where it is produced. (No cell
exchanges anything between chips.)
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def failing(line: dict) -> set:
    return {c["name"] for c in line["compared"] if c["value"] > c["limit"]}


# ---- compact10m.fill_compact: the engine is in the run's own process


def run_compact(monkeypatch, capsys, seed: int) -> dict:
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run_under_test", RUN)
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    monkeypatch.setattr(sys, "argv", [
        RUN, "--workload", "compact10m.fill_compact", "--seed", str(seed),
        "--seconds", "1", "--trace", "0", "--rehearsal"])
    assert run.main() == 0
    return last_line(capsys.readouterr().out)


def test_compact_sound_run_is_correct(monkeypatch, capsys):
    line = run_compact(monkeypatch, capsys, 2_147_483_801)
    assert line["correct"] is True and not failing(line)


def test_compact_step_that_leaves_its_state_unchanged(monkeypatch, capsys):
    from pegasus_tpu.engine import LsmEngine

    monkeypatch.setattr(
        LsmEngine, "manual_compact",
        lambda self, **kw: {"input_records": 0, "output_records": 0})
    line = run_compact(monkeypatch, capsys, 2_147_483_802)
    assert line["correct"] is False
    assert {"rows_differing", "step_count_gaps"} <= failing(line)


def test_compact_half_of_the_batch_left_out(monkeypatch, capsys):
    from pegasus_tpu.engine import LsmEngine

    real, calls = LsmEngine.install_ingested_block, [0]

    def every_other(self, block):
        calls[0] += 1
        if calls[0] % 2:
            real(self, block)

    monkeypatch.setattr(LsmEngine, "install_ingested_block", every_other)
    line = run_compact(monkeypatch, capsys, 2_147_483_803)
    assert line["correct"] is False
    assert "rows_differing" in failing(line)


def test_compact_an_answer_altered_where_it_is_produced(monkeypatch, capsys):
    from pegasus_tpu.engine import db

    real = db.write_sst

    def one_byte_off(path, block, meta=None, **kw):
        if not (meta or {}).get("ingested") and block.n:
            arena = block.val_arena.copy()
            arena[-1] ^= 1              # crc is taken over what is written
            block = block.gather(range(block.n))
            block.val_arena = arena
        return real(path, block, meta, **kw)

    monkeypatch.setattr(db, "write_sst", one_byte_off)
    line = run_compact(monkeypatch, capsys, 2_147_483_804)
    assert line["correct"] is False
    assert failing(line) == {"rows_differing"} or \
        failing(line) == {"rows_differing", "point_reads_wrong"}


# ---- ycsb1kb.a and .c: the engine is in the server process;
# lib/serverproc.py plants the fault there when BENCH_FAULT is set


def run_served(seed: int, fault: str = None, cell: str = "ycsb1kb.a") -> dict:
    env = dict(os.environ)
    if fault:
        env["BENCH_FAULT"] = fault
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = last_line(proc.stdout)
    # every number compared is on stderr too, beside its limit
    for c in line["compared"]:
        assert f"compared {c['name']}: {c['value']} (limit {c['limit']})" \
            in proc.stderr
    return line


def test_served_update_that_leaves_the_state_unchanged():
    line = run_served(2_147_483_811, "drop_update")
    assert line["correct"] is False
    assert "updates_lost" in failing(line)


def test_served_answer_altered_where_it_is_produced():
    line = run_served(2_147_483_812, "alter_answer")
    assert line["correct"] is False
    assert "reads_wrong" in failing(line)


def test_read_only_cell_answer_altered_where_it_is_produced():
    """`ycsb1kb.c`: the sweep's and the window's reads are all it has."""
    line = run_served(2_147_483_813, "alter_answer", "ycsb1kb.c")
    assert line["correct"] is False
    assert "reads_wrong" in failing(line)


def test_read_only_cell_whose_load_left_the_state_unchanged():
    """`ycsb1kb.c` writes only in its load: a decree applied as empty there
    is a record that no read finds and that the audit does not count."""
    line = run_served(2_147_483_814, "drop_update", "ycsb1kb.c")
    assert line["correct"] is False
    assert {"reads_wrong", "untouched_changed"} <= failing(line)
