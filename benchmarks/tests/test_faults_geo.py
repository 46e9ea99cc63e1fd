"""`geo1m.radial500` with the served path broken underneath (the faults
lib/serverproc.py plants when BENCH_FAULT is set, as test_faults.py has
them for the ycsb cells): `correct` has to come out false, by
`searches_wrong` or `points_unreachable`; a sound run reads 0 in both."""

from benchmarks.tests.test_faults import failing, run_served

CELL = "geo1m.radial500"


def test_geo_sound_run_is_correct():
    line = run_served(2_147_483_820, cell=CELL)
    assert line["correct"] is True and not failing(line)
    assert {c["name"] for c in line["compared"]} == {
        "searches_wrong", "points_unreachable", "replicas_differing",
        "audit_record_gap", "long_key_bypass", "guard_totals_moved",
        "compiles_in_window"}


def test_geo_rows_dropped_at_load():
    """Every 7th decree acknowledged and applied as empty on every
    replica: index rows (and common rows) that no search or get finds."""
    line = run_served(2_147_483_821, "drop_update", CELL)
    assert line["correct"] is False
    assert {"searches_wrong", "points_unreachable"} & failing(line)
    assert "audit_record_gap" in failing(line)


def test_geo_point_altered_where_it_is_produced():
    """Every 13th point read that finds a value answers it with one byte
    flipped: a sampled point's `get` on the common table is not its value."""
    line = run_served(2_147_483_822, "alter_answer", CELL)
    assert line["correct"] is False
    assert "points_unreachable" in failing(line)


# ---- the runner's own reading of a trigger_audit report


def _report(records=(10, 20, 30, 40), inconclusive=(), mismatches=(),
            short=()):
    """A shell report of 4 partitions x 3 replicas; partitions in
    `inconclusive` never got past their primary, those in `short` lack a
    secondary's digest."""
    digests, primaries, inc = {}, {}, []
    for p, n in enumerate(records):
        gpid = f"3.{p}"
        if p in inconclusive:
            inc.append({"gpid": gpid, "reason": "primary unreachable"})
            continue
        nodes = ["a", "b", "c"][:2 if p in short else 3]
        digests[gpid] = {x: {"decree": 7, "digest": f"d{p}"} for x in nodes}
        primaries[gpid] = {"node": "a", "decree": 7, "digest": f"d{p}",
                           "records": n}
    for p in short:
        inc.append({"gpid": f"3.{p}", "node": "c", "reason": "no digest"})
    return {"partitions": 4, "ok": [], "mismatches": list(mismatches),
            "inconclusive": inc, "digests": digests, "primaries": primaries}


def _audit(reports, tries=3):
    """GeoDeployment.audit over a shell that prints `reports` in turn
    -> (its verdict, the lines it typed)."""
    import json
    import types

    from benchmarks.runners import onebox_geo

    typed, said = [], []
    dep = object.__new__(onebox_geo.GeoDeployment)
    dep.ctx = types.SimpleNamespace(
        workload={"audit": {"timeout_s": 60, "tries": tries}},
        say=lambda msg, **kw: said.append(msg))
    dep.table, dep.name, dep.records = {"replicas": 3, "partitions": 4}, "t", 100
    dep.server = types.SimpleNamespace(alive=lambda: True,
                                       log_tail=lambda n=40: "")
    left = list(reports)

    def shell_line(line):
        typed.append(line)
        return json.dumps(left.pop(0), indent=1) + "\naudit ...\n"

    dep.shell_line = shell_line
    return dep.audit(), typed


def test_audit_sound_report_is_asked_once():
    got, typed = _audit([_report()])
    assert got == {"replicas_differing": 0, "audit_record_gap": 0}
    assert typed == ["trigger_audit t 60"]


def test_audit_inconclusive_is_asked_again_and_the_last_report_judged():
    got, typed = _audit([_report(inconclusive=(2, 3)), _report()])
    assert got == {"replicas_differing": 0, "audit_record_gap": 0}
    assert len(typed) == 2
    # ... and stays not correct when no try can tell, as the driver's run
    # of seed 2052172470 read it: 2 partitions unheard -> 4, their records
    got, typed = _audit([_report(inconclusive=(2, 3))] * 3)
    assert got == {"replicas_differing": 4, "audit_record_gap": 70}
    assert len(typed) == 3
    got, _ = _audit([_report(short=(1,))] * 3)
    assert got == {"replicas_differing": 2, "audit_record_gap": 0}


def test_audit_mismatch_or_lost_records_are_never_asked_twice():
    bad = {"gpid": "3.1", "node": "b", "digest": "x", "expected": "d1"}
    got, typed = _audit([_report(mismatches=(bad,), inconclusive=(3,)),
                         _report()])
    assert got["replicas_differing"] >= 1 and len(typed) == 1
    got, typed = _audit([_report(records=(10, 20, 30, 39)), _report()])
    assert got == {"replicas_differing": 0, "audit_record_gap": 1}
    assert len(typed) == 1
