"""The plain reference of the geo deployment: the points a seed makes, the
great-circle distance, and a radial search by brute force over all of
them. It knows no cells, no covering, no index keys and no table: a search
is every point whose distance from the centre is at most the radius.
Imports nothing of the program and takes nothing the program has made.

A point is (latitude, longitude, owner key, value): the owner key is the
point's record in the common table (record i as `ycsb1kb` keys it), the
value ten '|'-separated fields of 100 bytes in all, longitude in field 4
and latitude in field 5 (upstream src/geo/lib/latlng_codec.h:35-55), the
record's tag (`pgv1`, record, writer, sequence) in field 0 as text, the
rest bytes only (seed, record, writer, sequence) give: any answer can be
checked on its own.
"""

import hashlib

import numpy as np

from .datagen import record_key

EARTH_RADIUS_M = 6371000.9      # the sphere upstream's S2 and the program use
BAND_M = 1e-6                   # a point this close to the radius may be on either side
SORTKEYS = 100                  # common-table sortkeys a hashkey, as ycsb1kb
VALUE_BYTES = 100
# upstream src/geo/bench/bench.cpp: the rectangle of Beijing's fifth ring road
RECT = {"lat": (39.810151, 40.028697), "lng": (116.194511, 116.535087)}


def points(seed: int, n: int, rect: dict = RECT):
    """-> (lat, lng): float64[n] each, uniform in the rectangle. The same
    seed gives the same points; point i is record i of the common table."""
    rng = np.random.default_rng([seed, 0x9E0])
    lat = rng.uniform(rect["lat"][0], rect["lat"][1], n)
    lng = rng.uniform(rect["lng"][0], rect["lng"][1], n)
    return lat, lng


def owner_key(seed: int, i: int):
    """-> (16 B hashkey, 8 B sortkey): record i of the common table."""
    return record_key(seed, i, SORTKEYS)


def make_value(seed: int, i: int, lat: float, lng: float, writer: int = 0,
               seq: int = 0) -> bytes:
    """The 100 bytes stored for point i in both tables. `repr` of a float
    reads back as the same float, so the coordinates lose nothing."""
    fill = hashlib.shake_128(b"geo:%d:%d:%d:%d" % (seed, i, writer, seq)
                             ).hexdigest(VALUE_BYTES // 2).encode()
    head = [b"pgv1.%d.%d.%d" % (i, writer, seq), fill[0:4], fill[4:8],
            fill[8:12], repr(float(lng)).encode(), repr(float(lat)).encode(),
            fill[12:16], fill[16:20], fill[20:24]]
    body = b"|".join(head) + b"|"
    assert len(body) < VALUE_BYTES, "a coordinate's text is too long"
    return body + fill[24:24 + VALUE_BYTES - len(body)]


def check_value(seed: int, value, lat=None, lng=None):
    """-> the record number when `value` is a whole value made for some
    record (and, where `lat`/`lng` arrays are given, carries that record's
    coordinates), else None."""
    if value is None or len(value) != VALUE_BYTES or value[:5] != b"pgv1.":
        return None
    try:
        _, rec, writer, seq = value.split(b"|", 1)[0].split(b".")
        i, writer, seq = int(rec), int(writer), int(seq)
        parts = value.split(b"|")
        vlat, vlng = float(parts[5]), float(parts[4])
    except (ValueError, IndexError):
        return None
    if lat is not None and not (0 <= i < len(lat) and vlat == lat[i]
                                and vlng == lng[i]):
        return None
    return i if value == make_value(seed, i, vlat, vlng, writer, seq) else None


def distance_m(lat, lng, lat2, lng2):
    """Great-circle distance in metres (haversine, float64); the second
    point may be arrays."""
    return _haversine(np.radians(lat), np.radians(lng), np.radians(lat2),
                      np.cos(np.radians(lat2)), np.radians(lng2))


def _haversine(p1, l1, p2, cos_p2, l2):
    a = (np.sin((p2 - p1) / 2.0) ** 2
         + np.cos(p1) * cos_p2 * np.sin((l2 - l1) / 2.0) ** 2)
    return 2.0 * EARTH_RADIUS_M * np.arcsin(np.minimum(1.0, np.sqrt(a)))


class Reference:
    """All points of one seed, and what a search has to answer."""

    def __init__(self, seed: int, n: int, rect: dict = RECT):
        self.seed, self.n = seed, n
        self.lat, self.lng = points(seed, n, rect)
        self._p, self._l = np.radians(self.lat), np.radians(self.lng)
        self._cos_p = np.cos(self._p)

    def value(self, i: int) -> bytes:
        return make_value(self.seed, i, self.lat[i], self.lng[i])

    def search(self, lat: float, lng: float, radius_m: float,
               block: int = 1 << 18):
        """-> (inside, band): the numbers of the points whose distance
        from (lat, lng) is at most the radius, and of those within BAND_M
        of it, which may be on either side. Brute force over all points,
        in blocks."""
        p1, l1 = np.radians(lat), np.radians(lng)
        inside, band = [], []
        for a in range(0, self.n, block):
            b = a + block
            d = _haversine(p1, l1, self._p[a:b], self._cos_p[a:b],
                           self._l[a:b])
            inside.append(a + np.flatnonzero(d <= radius_m))
            band.append(a + np.flatnonzero(np.abs(d - radius_m) <= BAND_M))
        return np.concatenate(inside), np.concatenate(band)

    def judge(self, lat: float, lng: float, radius_m: float, rows) -> bool:
        """Whether `rows`, an answer's [(hash_key, sort_key, value)], is
        the search's answer: every row a whole stored point under its
        owner's key, no point twice, and the set of points the
        reference's, give or take those in the band."""
        inside, band = self.search(lat, lng, radius_m)
        got = set()
        for hk, sk, value in rows:
            i = check_value(self.seed, value, self.lat, self.lng)
            if i is None or (hk, sk) != owner_key(self.seed, i) or i in got:
                return False
            got.add(i)
        return not (got ^ set(inside.tolist())) - set(band.tolist())
