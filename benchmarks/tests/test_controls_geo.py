"""The geo cell's controls (controls_geo.py): the reference put in the
system's place with one stated guarantee broken has to come out as not
correct, on three seeds, by the comparisons the runs use; sound, it reads
0. And the reference itself: its distance against a hand-worked one, its
value against its own check."""

import math

import pytest

from benchmarks.lib import reference_geo
from benchmarks.tests import controls_geo

SEEDS = (3, 2_147_483_777, 4_000_000_019)
# dense enough at 60,000 points that a 2 km search returns some 400
SMALL = dict(points=60_000, searches=12, radius_m=2000.0, sample=300)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("broken", ["index_row_dropped", "point_altered"])
def test_geo_control_is_not_correct(seed, broken):
    sound = controls_geo.geo_control(seed, None, **SMALL)
    assert sound["searches_wrong"] == sound["points_unreachable"] == 0
    assert sound["points_returned"] > 1000
    bad = controls_geo.geo_control(seed, broken, **SMALL)
    assert bad["searches_wrong"] > 0
    if broken == "index_row_dropped":
        assert bad["points_unreachable"] > 0


def test_reference_distance_and_values():
    # a degree of latitude on the reference's sphere
    one = reference_geo.distance_m(39.0, 116.0, 40.0, 116.0)
    assert abs(one - math.radians(1.0) * reference_geo.EARTH_RADIUS_M) < 1e-6
    assert reference_geo.distance_m(39.9, 116.4, 39.9, 116.4) == 0.0
    want = reference_geo.Reference(5, 1000)
    inside, band = want.search(39.92, 116.36, 3000.0)
    d = reference_geo.distance_m(39.92, 116.36, want.lat, want.lng)
    assert sorted(inside) == [i for i in range(1000) if d[i] <= 3000.0]
    assert len(band) == 0 and len(inside) > 5
    for i in (0, 7, 999):
        v = want.value(i)
        assert len(v) == 100 and v.count(b"|") == 9
        assert float(v.split(b"|")[5]) == want.lat[i]
        assert float(v.split(b"|")[4]) == want.lng[i]
        assert reference_geo.check_value(5, v, want.lat, want.lng) == i
        assert reference_geo.check_value(5, v[:-1] + b"!") is None
        assert reference_geo.check_value(6, v) is None
