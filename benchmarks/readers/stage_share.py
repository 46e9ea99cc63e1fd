"""One stage's share of manual_compact's wall time over all steps, from
the per-stage seconds manual_compact returns in stats["trace"]."""


def read(observed: dict, params: dict):
    steps = [s for s in observed.get("steps", ())
             if params["stage"] in s.get("stages", {})]
    wall = sum(s["manual_compact_s"] for s in steps)
    if not steps or wall <= 0:
        return None
    return 100.0 * sum(s["stages"][params["stage"]] for s in steps) / wall
