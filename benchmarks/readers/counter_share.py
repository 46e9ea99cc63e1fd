"""The windowed difference of one server counter as a share, in %, of the
summed differences of several (itself among them): how much of a kind of
work one path took. A program that does not publish the counter has
nothing to read, and the line leaves the metric out."""

from benchmarks.readers.counter_ratio import delta


def read(observed: dict, params: dict):
    num = delta(observed, params["num"])
    parts = [delta(observed, name) for name in params["of"]]
    if num is None or None in parts or not sum(parts):
        return None
    return 100.0 * num / sum(parts)
