"""What manual_compact's stages leave unnamed: 100 x (1 - the summed
seconds of `params.stages` over manual_compact's wall time), all steps.
`stages` lists stages that do not nest in one another (the leaves of
stats["trace"]), so their sum counts no second twice; a stage a step did
not close counts as zero. Only steps that report every stage of
`params.requires` are read: a program that does not open those spans has
not set out to name the remainder, and its line leaves the metric out."""


def read(observed: dict, params: dict):
    steps = [s for s in observed.get("steps", ())
             if all(st in s.get("stages", {}) for st in params["requires"])]
    wall = sum(s["manual_compact_s"] for s in steps)
    if not steps or wall <= 0:
        return None
    named = sum(s["stages"].get(st, 0.0)
                for s in steps for st in params["stages"])
    return 100.0 * (1.0 - named / wall)
