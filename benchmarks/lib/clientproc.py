"""One load-generating process of a served cell: a few closed-loop client
threads, each on its own PegasusClient over sockets, none importing jax.
The one general generator: everything it does is in the spec file.

    python3 benchmarks/lib/clientproc.py <spec.json>

Spec: metas, table, seed, process, threads, writer_base, records,
sortkeys, value_bytes, theta, mix {read, update}, seconds, timeout_s,
control (directory: this process writes `ready.<process>`, waits for `go`,
which holds the wall-clock start), out (result path).

Every thread draws its record numbers (scrambled zipfian) and operation
kinds from (seed, process, thread) before the start. A read's answer is
checked where it arrives: it must be a whole value some writer made for
that record, and not older than this thread's own last acknowledged
update of it. Each operation is timed on this host's monotonic clock; one
that raises counts as failed, with the client's timeout as its latency.
"""

import json
import os
import resource
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.lib import datagen, markers  # noqa: E402

DRAW = 1 << 16   # operations drawn at a time


class Worker:
    def __init__(self, spec: dict, thread: int, cli=None):
        self.spec = spec
        self.writer = spec["writer_base"] + spec["process"] * spec["threads"] \
            + thread
        self.rng = np.random.default_rng(
            [spec["seed"], spec["writer_base"], spec["process"], thread])
        self.zipf = datagen.ZipfKeys(spec["records"], spec["theta"])
        self.cli = cli or self.connect()
        self.lat = {"read": [], "update": []}
        self.at = {"read": [], "update": []}   # issue time, s from start
        self.done = {"read": 0, "update": 0}
        self.failed = {"read": 0, "update": 0}
        self.wrong = 0
        self.errors = []
        self.acked, self.attempted, self.seq = {}, {}, 0
        self.draw()

    def connect(self):
        from pegasus_tpu.client import MetaResolver, PegasusClient

        return PegasusClient(MetaResolver(self.spec["metas"],
                                          self.spec["table"]),
                             timeout=self.spec["timeout_s"])

    def report(self) -> dict:
        return {"writer": self.writer, "lat": self.lat, "at": self.at,
                "done": self.done,
                "failed": self.failed, "wrong": self.wrong,
                "errors": self.errors,
                "acked": {str(k): v for k, v in self.acked.items()},
                "attempted": {str(k): v for k, v in self.attempted.items()}}

    def draw(self) -> None:
        self.recs = self.zipf.scrambled(self.rng, DRAW)
        self.reads = self.rng.random(DRAW) < self.spec["mix"]["read"]
        self.k = 0

    def one(self, end: float) -> None:
        if self.k == DRAW:
            self.draw()
        spec = self.spec
        i, read = int(self.recs[self.k]), bool(self.reads[self.k])
        self.k += 1
        kind = "read" if read else "update"
        hk, sk = datagen.record_key(spec["seed"], i, spec["sortkeys"])
        if not read:
            self.seq += 1
            value = datagen.make_value(spec["seed"], i, self.writer, self.seq,
                                       spec["value_bytes"])
            self.attempted[i] = self.seq
        t0 = time.monotonic()
        self.at[kind].append(t0 - self.start)
        try:
            if read:
                got = self.cli.get(hk, sk)
            else:
                self.cli.set(hk, sk, value)
        except Exception as e:  # noqa: BLE001 - counted; the run reports it
            self.failed[kind] += 1
            self.lat[kind].append(spec["timeout_s"] * 1000.0)
            if len(self.errors) < 3:
                self.errors.append(repr(e))
            return
        t1 = time.monotonic()
        self.lat[kind].append((t1 - t0) * 1000.0)
        if t1 <= end:
            self.done[kind] += 1
        if read:
            who = datagen.check_value(spec["seed"], i, got, spec["value_bytes"])
            if who is None or (who[0] == self.writer
                               and who[1] < self.acked.get(i, 0)):
                self.wrong += 1
        else:
            self.acked[i] = self.seq

    def loop(self, start: float, end: float) -> None:
        self.start = start
        while time.monotonic() < start:
            time.sleep(0.0005)
        try:
            while time.monotonic() < end:
                self.one(end)
        finally:
            self.cli.close()


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    workers = [Worker(spec, t) for t in range(spec["threads"])]
    ctl = spec["control"]
    markers.put(os.path.join(ctl, f"ready.{spec['process']}"))
    start_wall = float(markers.wait(os.path.join(ctl, "go"), poll_s=0.005))
    start = time.monotonic() + (start_wall - time.time())
    end = start + spec["seconds"]
    threads = [threading.Thread(target=w.loop, args=(start, end), daemon=True)
               for w in workers]
    late = max(0.0, time.monotonic() - start)
    cpu0 = resource.getrusage(resource.RUSAGE_SELF)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cpu1 = resource.getrusage(resource.RUSAGE_SELF)
    out = {"process": spec["process"], "late_s": late,
           "cpu_s": (cpu1.ru_utime + cpu1.ru_stime
                     - cpu0.ru_utime - cpu0.ru_stime),
           "workers": [w.report() for w in workers]}
    markers.put(spec["out"], json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
