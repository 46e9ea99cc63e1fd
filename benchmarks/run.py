#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--rehearsal]

Everything that belongs to one cell is data this file finds by name, from
BENCHMARK.json down: the cell's traffic mix (workloads/<cell>.json), its
configuration (the `file` BENCHMARK.json gives), the runner the
configuration names (runners/<runner>.py) and, for each per-layer metric
that lists the cell, its description (metrics/<name>.json) and the reader
that names (readers/<reader>.py). A new cell, configuration or metric is
new files plus entries in BENCHMARK.json; nothing here changes.

The last line of standard output is the result object. Without
--rehearsal a run that finds no TPU, or fewer chips than the cell asks
for, exits non-zero and prints no result. With it the same code runs on
XLA:CPU at the configuration's `rehearsal` sizes and says so in `device`:
control flow and answers only, never a number to record.
"""

import time

T0 = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"{what} {name!r} is not in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Context:
    """What a runner gets: the cell's data, the arguments, the clock."""

    def __init__(self, ns, cell, config, workload):
        self.cell, self.config, self.workload = cell, config, workload
        self.seed, self.seconds = ns.seed, ns.seconds
        self.trace, self.rehearsal = bool(ns.trace), ns.rehearsal
        self.root, self.here, self.t0 = ROOT, HERE, T0

    def scale(self, key: str):
        """One of the configuration's top-level sizes (those `reduced` may
        name), at the rehearsal's size when this is one."""
        if self.rehearsal and key in self.config.get("rehearsal", {}):
            return self.config["rehearsal"][key]
        return self.config[key]

    def say(self, msg: str, **fields) -> None:
        tail = (" " + json.dumps(fields, sort_keys=True, default=str)
                ) if fields else ""
        print(f"[{time.monotonic() - T0:7.1f}s] {msg}{tail}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ns = ap.parse_args()
    if ns.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = named(manifest["workloads"], ns.workload, "workload")
    config = load_json(ROOT, named(manifest["configs"], cell["config"],
                                   "configuration")["file"])
    workload = load_json(HERE, "workloads", cell["name"] + ".json")
    runner = load_module("runners", config["runner"])
    ctx = Context(ns, cell, config, workload)
    ctx.say(f"{cell['name']}: seed {ns.seed}, window {ns.seconds:g}s, "
            f"trace {ns.trace}" + (", REHEARSAL on the cpu" if ns.rehearsal
                                   else ""))
    try:
        res = runner.run(ctx)
    except BaseException:  # noqa: BLE001 - reported; the run then fails
        traceback.print_exc()
        print("run failed before a result: see the traceback above",
              file=sys.stderr, flush=True)
        return 1

    metrics = {}
    if ns.trace:
        for m in manifest["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            desc = load_json(HERE, "metrics", m["name"] + ".json")
            value = load_module("readers", desc["reader"]).read(
                res["observed"], desc.get("params", {}))
            if value is not None:   # nothing to read: left out, never 0
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest["end_to_end"]:
            if applies(m, cell["name"]) and m["name"] in res["end_to_end"]:
                metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    compared = [{"name": n, "value": v, "limit": lim}
                for n, v, lim in res["compared"]]
    holds = [c["value"] is not None and c["value"] <= c["limit"]
             for c in compared]
    correct = all(holds)
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": res["device"]}
    if ns.trace and res.get("breakdown"):
        line["breakdown"] = res["breakdown"]
    if ns.rehearsal:
        line["rehearsal"] = True
    line["compared"] = compared
    for note in res.get("notes", ()):
        print(note, flush=True)
    sys.stdout.flush()
    for c, ok in zip(compared, holds):
        print(f"compared {c['name']}: {c['value']} (limit {c['limit']})"
              + ("" if ok else "  <-- NOT CORRECT"), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
