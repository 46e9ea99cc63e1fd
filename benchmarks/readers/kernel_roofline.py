"""A kernel's share of its roofline: the least time the chip could take
for the bytes the algorithm has to move (counted from the shapes by
lib/roofline.py) over the device time the trace shows for it."""

from benchmarks.lib import roofline
from benchmarks.readers.trace_program import matching


def read(observed: dict, params: dict):
    trace, count, total_s = matching(observed, params["prefix"])
    shapes, peaks = observed.get(params["shapes"]), observed.get("peaks")
    if not count or not shapes or not peaks or total_s <= 0:
        return None
    least_bytes = getattr(roofline, params["bytes"])(**shapes) * trace["steps"]
    return 100.0 * (least_bytes / peaks[params["peak"]]) / total_s
