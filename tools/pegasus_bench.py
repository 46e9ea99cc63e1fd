"""pegasus_bench: db_bench-style op lanes through the serving stack.

The reference harness shape (src/test/bench_test/benchmark.cpp:210-215 +
scripts/pegasus_bench_run.sh:25-44): named benchmarks run in sequence over
a shared table, each reporting QPS + avg + P99 latency per thread count.

    python tools/pegasus_bench.py --benchmarks fillseq_pegasus,\
fillrandom_pegasus,readrandom_pegasus,deleterandom_pegasus \
        --num 10000 --threads 1,4 --value-size 1000 [--meta host:port]

(no --meta: boots an in-process onebox). One JSON line per (benchmark,
thread-count), mirroring pegasus_bench_run.sh's thread sweep.
"""

import argparse
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


KNOWN_BENCHMARKS = ("scan_pegasus", "multisetrandom_pegasus",
                    "multigetrandom_pegasus",
                    "fillseq_pegasus", "fillrandom_pegasus",
                    "readrandom_pegasus", "deleterandom_pegasus")


def run_lane(name, meta_addr, table, n_per_thread, n_threads, value_size):
    from pegasus_tpu.client import MetaResolver, PegasusClient, PegasusError

    value = bytes(value_size)
    errors = [0] * n_threads
    lats = [[] for _ in range(n_threads)]

    def op_fn(cli, tid, rng):
        if name == "fillseq_pegasus":
            seq = [0]

            def op():
                i = seq[0]
                seq[0] += 1
                cli.set(b"bk%02d%08d" % (tid, i), b"s", value)
        elif name == "fillrandom_pegasus":
            def op():
                cli.set(b"bk%02d%08d" % (tid, rng.randrange(n_per_thread)),
                        b"s", value)
        elif name == "readrandom_pegasus":
            def op():
                cli.get(b"bk%02d%08d" % (tid, rng.randrange(n_per_thread)),
                        b"s")
        elif name == "deleterandom_pegasus":
            def op():
                cli.delete(b"bk%02d%08d" % (tid, rng.randrange(n_per_thread)),
                           b"s")
        elif name == "multisetrandom_pegasus":
            # reference pegasus_bench multi_set: 10 sortkeys per op under
            # one hash key (one batched write RPC / one decree)
            def op():
                hk = b"mk%02d%06d" % (tid, rng.randrange(n_per_thread))
                cli.multi_set(hk, {b"s%02d" % i: value for i in range(10)})
        elif name == "multigetrandom_pegasus":
            def op():
                hk = b"mk%02d%06d" % (tid, rng.randrange(n_per_thread))
                cli.multi_get(hk)
        else:
            raise ValueError(f"unknown benchmark {name}")
        return op

    # clients (meta resolution included) are built BEFORE the clock starts:
    # boot-up RPCs must not deflate small runs' QPS
    clients = [PegasusClient(MetaResolver([meta_addr], table), timeout=15)
               for _ in range(n_threads)]

    def worker(tid):
        rng = random.Random(tid * 7919)
        cli = clients[tid]
        op = op_fn(cli, tid, rng)
        for _ in range(n_per_thread):
            t0 = time.perf_counter()
            try:
                op()
            except PegasusError:
                errors[tid] += 1
            lats[tid].append((time.perf_counter() - t0) * 1e6)

    t_start = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t_start
    for cli in clients:
        cli.close()
    flat = sorted(x for lane in lats for x in lane)
    total = len(flat)
    return {
        "benchmark": name, "threads": n_threads,
        "qps": round(total / elapsed, 1),
        "avg_us": round(sum(flat) / max(1, total), 1),
        "p99_us": round(flat[min(total - 1, int(total * 0.99))], 1) if flat else 0,
        "ops": total, "errors": sum(errors),
        "value_size": value_size,
    }


def run_scan_lane(meta_addr, table, n_threads):
    """Full-table scan throughput (the copy_data / backup / bulk-export
    shape, reference scan_data in pegasus_bench): every partition's
    unordered scanner drained, split over n_threads."""
    from pegasus_tpu.client import MetaResolver, PegasusClient

    cli = PegasusClient(MetaResolver([meta_addr], table), timeout=15)
    scanners = cli.get_unordered_scanners()
    counts = [0] * n_threads
    lock = threading.Lock()
    queue = list(scanners)

    def worker(tid):
        while True:
            with lock:
                if not queue:
                    return
                sc = queue.pop()
            for _ in sc:
                counts[tid] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    cli.close()
    total = sum(counts)
    return {"benchmark": "scan_pegasus", "threads": n_threads,
            "qps": round(total / elapsed, 1), "ops": total,
            "errors": 0, "elapsed_s": round(elapsed, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--meta", default="")
    ap.add_argument("--table", default="bench")
    ap.add_argument("--benchmarks",
                    default="fillseq_pegasus,fillrandom_pegasus,"
                            "readrandom_pegasus,deleterandom_pegasus")
    ap.add_argument("--num", type=int, default=10_000)
    ap.add_argument("--threads", default="1")
    ap.add_argument("--value-size", type=int, default=1000)
    ap.add_argument("--partitions", type=int, default=8)
    args = ap.parse_args()

    names = [n.strip() for n in args.benchmarks.split(",") if n.strip()]
    unknown = [n for n in names if n not in KNOWN_BENCHMARKS]
    if unknown:
        # fail LOUDLY before any thread spawns: a typo must not emit a
        # plausible-looking zero-QPS JSON line with exit code 0
        print(f"unknown benchmark(s) {unknown}; known: "
              f"{', '.join(KNOWN_BENCHMARKS)}", file=sys.stderr)
        sys.exit(2)
    from tools._onebox import resolve_cluster

    meta_addr, box = resolve_cluster(args.meta, args.table, args.partitions)
    try:
        for n_threads in (int(t) for t in args.threads.split(",")):
            for name in names:
                if name == "scan_pegasus":
                    out = run_scan_lane(meta_addr, args.table, n_threads)
                else:
                    out = run_lane(name, meta_addr, args.table,
                                   args.num, n_threads, args.value_size)
                print(json.dumps(out), flush=True)
    finally:
        if box is not None:
            box.stop()


if __name__ == "__main__":
    main()
