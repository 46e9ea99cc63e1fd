"""The range kernel's share of its roofline: the least time the chip
could take for the bytes its calls have to move (lib/roofline_range.py,
from the run's shape and the mean number of ranges a call carried in the
window) over the device time the trace shows for them. Well under 1 %:
the kernel is bound by latency, not by bytes; the metric is there so that
a later kernel cannot read over 100 %."""

from benchmarks.lib import roofline_range
from benchmarks.readers.counter_ratio import delta
from benchmarks.readers.trace_program import matching


def read(observed: dict, params: dict):
    _, count, total_s = matching(observed, params["prefix"])
    shapes, peaks = observed.get(params["shapes"]), observed.get("peaks")
    ranges, calls = (delta(observed, params[k]) for k in ("ranges", "calls"))
    if not count or not shapes or not peaks or total_s <= 0 \
            or not ranges or not calls:
        return None
    least = count * roofline_range.range_least_bytes(ranges / calls, **shapes)
    return 100.0 * (least / peaks[params["peak"]]) / total_s
