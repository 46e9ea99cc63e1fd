"""The controls, runnable at any size: the plain reference in the system's
place with ONE guarantee of the configuration broken, judged by the
comparisons the runs use. test_controls.py runs them small; at the cells'
own sizes (on the chip's host, three seeds):

    python3 benchmarks/tests/controls.py compact10m 3
    python3 benchmarks/tests/controls.py ycsb1kb 3
    python3 benchmarks/tests/controls.py ycsb1kb 3 c     (the mix of ycsb1kb.c)
"""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.lib import clientproc, datagen, reference  # noqa: E402
from benchmarks.runners import onebox_serve  # noqa: E402


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def flat(out: dict) -> dict:
    return {"keys": out["keys"].reshape(-1), "vals": out["vals"].reshape(-1),
            "expire": out["expire"]}


def compaction_control(seed: int, fill: dict, now: int, broken: str) -> dict:
    """`oldest_version_kept`: dedup keeps the oldest version of a key, not
    the newest. `expired_kept`: records whose TTL has passed stay."""
    runs = datagen.fill_runs(seed, fill)
    want = reference.compact(runs, now)
    got = reference.compact(runs, now,
                            keep="oldest" if broken == "oldest_version_kept"
                            else "newest",
                            drop_expired=broken != "expired_kept")
    rng = np.random.default_rng([seed, 99])
    keys = np.concatenate([r["keys"][rng.integers(0, len(r["keys"]), 500)]
                           for r in runs])
    table = {bytes(k): bytes(v) for k, v in zip(got["keys"], got["vals"])}
    answers = reference.point_answers(runs, now, keys)
    return {"rows_differing": reference.differing_rows(flat(want), flat(got)),
            "point_reads_wrong": sum(table.get(bytes(k)) != a
                                     for k, a in zip(keys, answers)),
            "reference_against_itself":
                reference.differing_rows(flat(want), flat(want))}


MIX_A = {"read": 0.5, "update": 0.5}


def served_control(seed: int, broken, hashkeys: int, seconds: float,
                   threads: int = 4, sortkeys: int = 100,
                   value_bytes: int = 1000, mix: dict = MIX_A) -> dict:
    """The cell's own client threads and judge against a ReferenceStore.
    `stale_reads`: a read does not see the newest acknowledged write.
    `lose_every`: an acknowledged write is stored nowhere. `alter_every`:
    an answer's bytes are not the bytes written. A mix that never updates
    (`ycsb1kb.c`) has acknowledged writes only in its load, so there the
    load goes through the store's `set`, where `lose_every` loses them."""
    knobs = {None: {}, "stale_reads": {"stale_reads": True},
             "lose_every": {"lose_every": 5},
             "alter_every": {"alter_every": 7}}[broken]
    store = reference.ReferenceStore(**knobs)
    records = hashkeys * sortkeys
    for i in range(records):
        hk, sk = datagen.record_key(seed, i, sortkeys)
        value = datagen.make_value(seed, i, 0, 0, value_bytes)
        if mix["update"]:
            store._rows[(hk, sk)] = value
        else:
            store.set(hk, sk, value)
    spec = {"seed": seed, "process": 0, "threads": threads, "writer_base": 1,
            "records": records, "sortkeys": sortkeys,
            "value_bytes": value_bytes, "theta": 0.99,
            "mix": mix, "timeout_s": 10.0}
    workers = [clientproc.Worker(spec, t, cli=store) for t in range(threads)]
    start = time.monotonic() + 0.01
    pool = [threading.Thread(target=w.loop, args=(start, start + seconds))
            for w in workers]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    phase = {"results": [{"workers": [w.report() for w in workers]}]}
    acked, attempted = onebox_serve.merge_acks([phase])
    ids, untouched = onebox_serve.records_to_read_back(seed, records,
                                                       attempted, 200)
    got = store.batch_get([datagen.record_key(seed, i, sortkeys)
                           for i in ids])
    out = onebox_serve.judge_final(seed, value_bytes, ids, got, acked,
                                   attempted, untouched)
    out["reads_wrong"] = sum(w.wrong for w in workers)
    out["operations"] = sum(sum(w.done.values()) for w in workers)
    return out


def main() -> int:
    name, n_seeds = sys.argv[1], int(sys.argv[2])
    cfg = load_config(name)
    mix = MIX_A
    if len(sys.argv) > 3:
        with open(os.path.join(ROOT, "benchmarks", "workloads",
                               f"{name}.{sys.argv[3]}.json")) as f:
            mix = json.load(f)["mix"]
    for seed in [2_147_483_900 + 7 * k for k in range(n_seeds)]:
        t = time.monotonic()
        if cfg["runner"] == "engine_compact":
            for broken in ("oldest_version_kept", "expired_kept"):
                print(name, seed, broken, json.dumps(compaction_control(
                    seed, dict(cfg["fill"], records=cfg["records"]),
                    cfg["compact_now"], broken)),
                    f"{time.monotonic() - t:.0f}s", flush=True)
        else:
            for broken in (None, "stale_reads", "lose_every", "alter_every"):
                if broken == "stale_reads" and not mix["update"]:
                    continue        # nothing is ever newer than the load
                print(name, mix, seed, broken, json.dumps(served_control(
                    seed, broken, cfg["hashkeys"], 20.0, threads=16,
                    sortkeys=cfg["table"]["sortkeys"],
                    value_bytes=cfg["table"]["value_bytes"], mix=mix)),
                    f"{time.monotonic() - t:.0f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
