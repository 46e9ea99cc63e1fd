"""Does the program under test keep a run of the geo index table's keys on
the device? Its own process, jax held to the cpu by the caller's
environment (the server process alone holds the chip):

    JAX_PLATFORMS=cpu python3 benchmarks/lib/geo_probe.py <empty directory>

Through the library surface: one `LsmEngine(backend="tpu")`, a few dozen
records under 51-byte keys (2 + 6 + 15 + 4 + 16 + 8, as
`GeoClient._geo_keys` makes them), flush, `manual_compact`, which primes
every SST it writes. The last line says what the program's own counter of
runs refused residency for their keys' length read afterwards, and whether
every SST holds a device read index.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from pegasus_tpu.base import key_schema
    from pegasus_tpu.engine.db import EngineOptions, LsmEngine
    from pegasus_tpu.runtime.perf_counters import counters

    eng = LsmEngine(os.path.join(sys.argv[1], "db"),
                    EngineOptions(backend="tpu"))
    try:
        keys = [key_schema.generate_key(
            b"e748%02x" % (i % 3), b"%015x" % (i * 7919) + b"0010"
            + b"userhash%08d" % i + b"sortkey%d" % (i % 10))
            for i in range(48)]
        assert {len(k) for k in keys} == {51}
        for k in keys:
            eng.put(k, b"\x82" + b"\x00" * 12 + b"v")
        eng.flush()
        eng.manual_compact(now=100)
        with eng._lock:
            ssts = [s for s in eng._all_ssts_locked() if s.n]
        out = {"long_key_bypass": counters.number(
                   "engine.hbm.long_key_bypass_count").value(),
               "resident": bool(ssts) and all(s.device_index is not None
                                              for s in ssts),
               "ssts": len(ssts)}
    finally:
        eng.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
