"""What a kernel has to move at the least, from the shapes handed to it:
the same count whatever implements the kernel."""


def merge_least_bytes(input_rows: int, output_rows: int,
                      key_bytes: int) -> int:
    """A k-way merge with dedup and the TTL/tombstone filter over
    device-resident key columns: every input row's sort columns read once
    (the key in 4-byte lanes, its length, expire time, tombstone flag) and
    one 4-byte survivor index written for every output row. Values never
    cross the device (`device_values` off), so they do not count."""
    lanes = -(-key_bytes // 4)
    row = 4 * lanes + 4 + 4 + 1
    return input_rows * row + output_rows * 4
