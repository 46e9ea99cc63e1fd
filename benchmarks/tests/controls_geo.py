"""The geo cell's controls, runnable at any size: the plain reference in
the system's place with ONE guarantee of `geo1m` broken ("a search returns
every point within the radius and no other"; "every acknowledged write is
... read back"), judged by the comparisons the runs use.
test_controls_geo.py runs them small; at the cell's own size, three seeds:

    python3 benchmarks/tests/controls_geo.py geo1m 3
"""

import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.lib import reference_geo  # noqa: E402


def geo_control(seed: int, broken, points: int, searches: int = 40,
                radius_m: float = 500.0, sample: int = 2000) -> dict:
    """`index_row_dropped`: every 5th point's index row was acknowledged
    at load and is stored nowhere, so no search finds the point.
    `point_altered`: one point of every answer comes back with a byte of
    its value changed. None: the reference as it is."""
    want = reference_geo.Reference(seed, points)
    rng = np.random.default_rng([seed, 77])

    def indexed(i: int) -> bool:
        return not (broken == "index_row_dropped" and i % 5 == 4)

    def answer(lat: float, lng: float, radius: float) -> list:
        inside, _ = want.search(lat, lng, radius)
        rows = [reference_geo.owner_key(seed, int(i)) + (want.value(int(i)),)
                for i in inside if indexed(int(i))]
        if broken == "point_altered" and rows:
            hk, sk, v = rows[0]
            rows[0] = (hk, sk, v[:-1] + bytes([v[-1] ^ 1]))
        return rows

    wrong = returned = 0
    for _ in range(searches):
        lat = rng.uniform(*reference_geo.RECT["lat"])
        lng = rng.uniform(*reference_geo.RECT["lng"])
        rows = answer(lat, lng, radius_m)
        returned += len(rows)
        wrong += not want.judge(lat, lng, radius_m, rows)
    unreachable = 0
    for i in random.Random(seed).sample(range(points), min(points, sample)):
        key = reference_geo.owner_key(seed, i)
        unreachable += key + (want.value(i),) not in answer(
            want.lat[i], want.lng[i], 1.0)
    return {"searches_wrong": wrong, "points_unreachable": unreachable,
            "searches": searches, "points_returned": returned}


def main() -> int:
    name, n_seeds = sys.argv[1], int(sys.argv[2])
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        cfg = json.load(f)
    for seed in [2_147_483_900 + 7 * k for k in range(n_seeds)]:
        for broken in (None, "index_row_dropped", "point_altered"):
            t = time.monotonic()
            print(name, seed, broken, json.dumps(geo_control(
                seed, broken, cfg["points"], searches=200)),
                f"{time.monotonic() - t:.0f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
