"""What the range kernel has to move at the least for one call, from the
shapes handed to it: the same count whatever implements the kernel.
(lib/roofline.py holds the merge's; this file came with the geo cell.)"""

import math

FENCE_MAX = 4096    # fence samples a run keeps at the most


def range_least_bytes(ranges: float, rows: int, key_bytes: int) -> float:
    """One call that resolves `ranges` (start, stop) bounds against one
    sorted run of `rows` keys of `key_bytes`, held as 4-byte lanes plus a
    4-byte length: per bound one lower_bound, which is a binary search of
    the fence samples (4 B each) and then of the rows between two samples
    (4·w + 4 B each); the two packed bounds read once; 8 B written."""
    row = 4 * -(-key_bytes // 4) + 4
    fence = min(FENCE_MAX, max(16, 1 << max(0, (max(1, rows // 8) - 1)
                                            .bit_length())))
    step = -(-rows // fence)
    lower_bound = (math.ceil(math.log2(fence)) * 4
                   + math.ceil(math.log2(max(2, step))) * row)
    return ranges * (2 * lower_bound + 2 * row + 8)
