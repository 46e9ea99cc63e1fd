"""From a profiler trace to numbers: device busy time, time per program
and per device operation, and the idle gaps by what the host was doing.

`load` turns the profiler's .xplane.pb into plain lists (plane -> line ->
(name, start_ns, duration_ns)); `reduce` works on those lists alone, so it
is checked against a small recorded trace kept in tests/ and computes the
same numbers for every later PR.

On a TPU host each chip is a plane "/device:TPU:<n>": its "XLA Ops" line
holds one event per operation that ran, its "XLA Modules" line one event
per program (`jit_pegasus_<kernel>(...)`). Host threads are the lines of
"/host:CPU"; the benchmark's own spans there start with "bench:". In a cpu
rehearsal there is no device plane, and the XLA:CPU client's threads stand
in for it so the same code runs.
"""

import glob
import os
import re
from bisect import bisect_left, bisect_right

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CPU_STANDIN_LINE = "tf_XLAPjRtCpuClient"


def load(log_dir: str) -> list:
    """-> [{"name": plane, "lines": [{"name": line, "events": [(name,
    start_ns, duration_ns), ...]}]}] from the newest trace under log_dir."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events]}
                       for line in plane.lines]}
            for plane in data.planes]


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def program_name(event_name: str) -> str:
    """`jit_pegasus_merge_cached(1234567)` -> `pegasus_merge_cached`."""
    name = event_name.split("(")[0].strip()
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """The trace names a device operation by its whole HLO line
    (`%fusion.3 = s32[...] fusion(...)`): keep what stands before ` = `."""
    return event_name.split(" = ")[0].lstrip("%")[:80]


def _device_lines(planes: list):
    """-> [(ops events, module events)] per chip; the cpu stand-in when
    there is no device plane."""
    chips = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        chips.append((lines.get(OPS_LINE, []), lines.get(MODULES_LINE, [])))
    if chips:
        return chips
    ops = [e for plane in planes if plane["name"] == HOST_PLANE
           for ln in plane["lines"] if ln["name"].startswith(CPU_STANDIN_LINE)
           for e in ln["events"]
           if e[2] > 0 and not e[0].startswith(("end: ", "Threadpool",
                                                "SlinkyThreadPool"))]
    return [(ops, [])] if ops else []


def reduce(planes: list, window_s: float) -> dict:
    """-> {window_s, busy_s, device_ops, programs, spans, idle_gaps}.
    busy_s is the union of the intervals in which an operation ran on a
    chip, averaged over the chips; programs maps a program's name to its
    event count and summed device seconds (summed over chips)."""
    chips = _device_lines(planes)
    host = [e for plane in planes if plane["name"] == HOST_PLANE
            for ln in plane["lines"]
            if not ln["name"].startswith(("tf_XLAEigen", CPU_STANDIN_LINE))
            for e in ln["events"] if e[2] > 0]
    bench = [e for e in host if e[0].startswith("bench:")]
    out = {"window_s": window_s, "busy_s": None, "device_ops": [],
           "programs": {}, "idle_gaps": [],
           "spans": _totals([(n[6:], d) for n, _, d in bench])}
    if not chips:
        return out
    index = _HostIndex(host)
    busy, op_s, gaps = 0.0, {}, {}
    for ops, modules in chips:
        merged = union([(s, s + d) for _, s, d in ops if d > 0])
        busy += sum(e - s for s, e in merged) / 1e9
        for name, _, d in ops:
            name = op_name(name)
            op_s[name] = op_s.get(name, 0.0) + d / 1e9
        for name, _, d in (modules or ()):
            p = out["programs"].setdefault(program_name(name),
                                           {"count": 0, "total_s": 0.0})
            p["count"] += 1
            p["total_s"] += d / 1e9
        idle = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for (start, end), what in zip(idle, index.doing(idle)):
            gaps[what] = gaps.get(what, 0.0) + (end - start) / 1e9
    out["busy_s"] = busy / len(chips)
    out["device_ops"] = _top(op_s)
    out["idle_gaps"] = _top(gaps)
    return out


class _HostIndex:
    """What the host was doing in an idle gap, found without walking every
    host event for every gap. Built once per `reduce`: the host events in
    order of their start, each with its place in the trace's own order
    (which breaks ties, as a plain walk over the trace would). `doing`
    then sweeps the gaps, which come in order of time, and keeps beside it
    only the events that are open at the moment it looks at."""

    def __init__(self, host: list):
        tagged = sorted((s, s + d, d, i, name)
                        for i, (name, s, d) in enumerate(host))
        self.events = tagged
        self.starts = [e[0] for e in tagged]
        self.bench = [e for e in tagged if e[4].startswith("bench:")]
        self.pegasus = [e for e in tagged if e[4].startswith("pegasus:")]

    def doing(self, gaps: list) -> list:
        """-> a name for each (start, end) of `gaps`, which are disjoint
        and ascending: the benchmark's innermost span over the gap's
        middle; else the program's innermost stage span (`pegasus:`) over
        it; else the host event that overlaps most of the gap; else
        `unattributed`."""
        mids = [(a + b) / 2 for a, b in gaps]
        names = []
        for (a, b), bench, stage, live in zip(
                gaps, _open_at(self.bench, mids), _open_at(self.pegasus, mids),
                _open_at(self.events, [a for a, _ in gaps])):
            over = bench or stage
            if over:        # innermost: the shortest, the trace's first of equals
                names.append(min(over, key=lambda e: (e[2], e[3]))[4])
                continue
            # overlap > 0 needs start < b and end > a: open at a, or
            # starting inside the gap
            lo, hi = bisect_right(self.starts, a), bisect_left(self.starts, b)
            best, best_s, best_i = "unattributed", 0.0, -1
            for s, e, _, i, name in live + self.events[lo:hi]:
                lap = min(b, e) - max(a, s)
                if lap > best_s or (lap == best_s and i < best_i):
                    best, best_s, best_i = name, lap, i
            names.append(best)
        return names


def _open_at(events: list, times: list):
    """For each of the ascending `times`, the events (sorted by start)
    with start <= t < end, as a list the caller must not keep."""
    live, i = [], 0
    for t in times:
        while i < len(events) and events[i][0] <= t:
            live.append(events[i])
            i += 1
        live = [e for e in live if e[1] > t]
        yield live


def _totals(pairs) -> dict:
    out = {}
    for name, d in pairs:
        out[name] = out.get(name, 0.0) + d / 1e9
    return out


def _top(by_name: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:n]]


def debug_dump(planes: list) -> None:
    """Where BENCH_TRACE_DESCRIBE / BENCH_TRACE_SAVE name a file: a text
    picture of the trace, and its earliest events as JSON (how the small
    recorded trace in tests/ was made)."""
    import json

    if os.environ.get("BENCH_TRACE_DESCRIBE"):
        with open(os.environ["BENCH_TRACE_DESCRIBE"], "w") as f:
            f.write("\n".join(describe(planes)))
    if os.environ.get("BENCH_TRACE_SAVE"):
        cut = [{"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                (n[:100], s, d) for n, s, d in sorted(
                    ln["events"], key=lambda e: e[1])[:300]]}
            for ln in p["lines"]]} for p in planes]
        with open(os.environ["BENCH_TRACE_SAVE"], "w") as f:
            json.dump(cut, f)


def describe(planes: list, per_line: int = 4) -> list:
    """A short text picture of a trace, for looking at one by hand."""
    out = []
    for plane in planes:
        out.append(f"PLANE {plane['name']}")
        for ln in plane["lines"]:
            evs = ln["events"]
            out.append(f"  LINE {ln['name']}: {len(evs)} events "
                       f"{[(n[:60], s, d) for n, s, d in evs[:per_line]]}")
    return out
