"""One partition replica's engine under the bulk-fill flow: a step opens a
fresh engine directory, ingests the cell's sorted runs through
`LsmEngine.install_ingested_block` (the IngestExternalFile seam the replica
uses for shell `start_bulk_load`), runs `LsmEngine.manual_compact` (shell
`manual_compact`) and closes. Steps run back to back, one at a time, whole,
until the window is over. One process, which holds the chip.

What is compared once the window has closed (every number beside its
limit, all exact): the SSTs the last step left on disk, read back through
a reopened engine, against the plain reference's output; every step's
record counts against the reference's; a seed-drawn sample of point reads
through the reopened engine; and that no lane-guard total moved and
nothing compiled inside the window.
"""

import json
import os
import shutil
import statistics
import tempfile
import time

import numpy as np

from benchmarks.lib import datagen, device, reference, tracered


def program_state() -> dict:
    """The totals that must stand still inside a window."""
    from pegasus_tpu.ops.kernel import compile_report
    from pegasus_tpu.runtime.lane_guard import LANE_GUARD

    lane, rep = LANE_GUARD.state(), compile_report()
    return dict(device.still_totals([lane], rep), compile=rep,
                lane={k: lane[k] for k in device.GUARD_TOTALS})


def one_step(path: str, opts, blocks: list, now: int) -> dict:
    """The timed unit: open, ingest every run, manual_compact, close."""
    from pegasus_tpu.engine import LsmEngine

    t0 = time.monotonic()
    with device.span("open"):
        eng = LsmEngine(path, opts)
    t1 = time.monotonic()
    with device.span("ingest"):
        for block in blocks:
            eng.install_ingested_block(block)
    t2 = time.monotonic()
    with device.span("manual_compact"):
        stats = eng.manual_compact(now=now)
    t3 = time.monotonic()
    with device.span("close"):
        eng.close()
    return {"path": path, "start": t0, "end": time.monotonic(),
            "ingest_s": t2 - t1, "manual_compact_s": t3 - t2,
            "input_records": int(stats["input_records"]),
            "output_records": int(stats["output_records"]),
            "stages": {k: v["s"] for k, v in stats.get("trace", {}).items()}}


def read_back(path: str):
    """The table a closed step left on disk -> (a reopened engine, with
    host options: nothing more is asked of the device; what its MANIFEST
    lists, read through the engine's SST reader: flat key and value bytes
    and the expire column, in read order)."""
    from pegasus_tpu.engine import EngineOptions, LsmEngine
    from pegasus_tpu.engine.sstable import SSTable

    eng = LsmEngine(path, EngineOptions())
    files = manifest_files(path)
    blocks = [SSTable(os.path.join(path, f)).block() for f in files]
    got = {"keys": np.concatenate([b.key_arena for b in blocks]),
           "vals": np.concatenate([b.val_arena for b in blocks]),
           "expire": np.concatenate([b.expire_ts for b in blocks]),
           "tombstones": int(sum(int(b.deleted.sum()) for b in blocks)),
           "files": len(files)}
    return eng, got


def manifest_files(path: str) -> list:
    """SST names in read order (L0 newest first, then the levels)."""
    with open(os.path.join(path, "MANIFEST")) as f:
        m = json.load(f)
    return list(m["l0"]) + [f for lv in sorted(m["levels"], key=int)
                            for f in m["levels"][lv]]


def sample_point_reads(eng, runs: list, seed: int, n: int, now: int) -> int:
    """n keys drawn from the seed over every run (live, shadowed, deleted
    and expired ones among them) plus keys no run holds, read through the
    engine; -> how many answers differ from the reference's."""
    rng = np.random.default_rng([seed, 99])
    keys = np.concatenate(
        [r["keys"][rng.integers(0, len(r["keys"]), size=n // len(runs))]
         for r in runs])
    absent = keys[: max(1, n // 20)].copy()
    absent[:, -1] ^= 0x5A
    keys = np.concatenate([keys, absent])
    want = reference.point_answers(runs, now, keys)
    got = eng.get_batch([bytes(k) for k in keys], now=now)
    return sum(1 for w, g in zip(want, got)
               if (None if g is None else bytes(g)) != w)


def run(ctx) -> dict:
    from pegasus_tpu.engine import EngineOptions

    cfg, wl = ctx.config, ctx.workload
    ident = device.identity(ctx)
    fill = dict(cfg["fill"], records=ctx.scale("records"))
    now = cfg["compact_now"]
    opts = EngineOptions(backend="tpu", **cfg["engine_options"])

    t = time.monotonic()
    runs = datagen.fill_runs(ctx.seed, fill)
    blocks = [datagen.to_kvblock(r) for r in runs]
    ctx.say(f"fill: {sum(len(r['keys']) for r in runs):,} records in "
            f"{len(runs)} sorted runs in {time.monotonic() - t:.1f}s")

    work = tempfile.mkdtemp(prefix="bench_compact_")
    trace_dir = os.path.join(work, "trace")
    try:
        t = time.monotonic()
        warm = one_step(os.path.join(work, "warm"), opts, blocks, now)
        shutil.rmtree(warm["path"])
        ctx.say(f"warm step (compiles or loads the kernels) "
                f"{time.monotonic() - t:.1f}s", compile=program_state()["compile"])

        before = program_state()
        steps = []
        with device.profiled(trace_dir, ctx.trace):
            window_start = time.monotonic()
            while time.monotonic() - window_start < ctx.seconds:
                with device.span("step"):
                    if steps:   # one table on disk at a time
                        with device.span("cleanup"):
                            shutil.rmtree(steps[-1]["path"])
                    steps.append(one_step(
                        os.path.join(work, f"step{len(steps)}"), opts,
                        blocks, now))
            window_end = steps[-1]["end"]
        after = program_state()
        peak = device.memory_peak_bytes()
        del blocks
        window_s = window_end - window_start
        ctx.say(f"window: {len(steps)} steps in {window_s:.2f}s",
                steps=[round(s["end"] - s["start"], 2) for s in steps])

        # ---- the comparison, outside set-up and the window
        t = time.monotonic()
        ref = reference.compact(runs, now)
        want = {"keys": ref["keys"].reshape(-1), "vals": ref["vals"].reshape(-1),
                "expire": ref["expire"]}
        eng, got = read_back(steps[-1]["path"])
        rows_differing = reference.differing_rows(want, got) + got["tombstones"]
        count_gaps = sum(abs(s["input_records"] - ref["input_records"])
                         + abs(s["output_records"] - len(ref["expire"]))
                         for s in steps)
        reads_wrong = sample_point_reads(eng, runs, ctx.seed,
                                         wl["point_read_sample"], now)
        eng.close()
        ctx.say(f"compared with the reference in {time.monotonic() - t:.1f}s",
                reference_output=len(ref["expire"]), files=got["files"])
        observed = {"steps": steps, "window_s": window_s,
                    "peaks": None, "trace": None,
                    "merge_shapes": {"input_rows": ref["input_records"],
                                     "output_rows": len(ref["expire"]),
                                     "key_bytes": runs[0]["keys"].shape[1]}}
        breakdown = None
        if ctx.trace:
            planes = tracered.load(trace_dir)
            red = tracered.reduce(planes, window_s)
            red["steps"] = len(steps)
            observed["trace"] = red
            if not ctx.rehearsal:
                observed["peaks"] = device.peaks(ident["kind"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            tracered.debug_dump(planes)
            ident = dict(ident, busy_s=red["busy_s"] or 0.0,
                         window_s=window_s)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    records = sum(s["input_records"] for s in steps)
    return {
        "attempted": len(steps), "failed": 0,
        "end_to_end": {"compact_rate": records / window_s,
                       "setup_s": window_start - ctx.t0},
        "observed": observed, "breakdown": breakdown,
        "device": dict(ident, memory_peak_bytes=peak),
        "compared": [
            ("rows_differing", rows_differing, 0),
            ("step_count_gaps", count_gaps, 0),
            ("point_reads_wrong", reads_wrong, 0),
            ("guard_totals_moved", after["guard"] - before["guard"], 0),
            ("compiles_in_window", after["compiles"] - before["compiles"], 0),
        ],
        "notes": [f"steps {[round(s['end'] - s['start'], 3) for s in steps]} "
                  f"median {statistics.median(s['end'] - s['start'] for s in steps):.3f}s; "
                  f"ingest {[round(s['ingest_s'], 2) for s in steps]} "
                  f"manual_compact {[round(s['manual_compact_s'], 2) for s in steps]} "
                  f"stages of the last {steps[-1]['stages']}; "
                  f"lane {after['lane']}; compile {after['compile']}"],
    }
