"""Device time of the programs whose name starts with `prefix`, from the
profiler trace: per event, or per step of the traced window, in ms."""


def matching(observed: dict, prefix: str):
    trace = observed.get("trace") or {}
    hit = [p for name, p in trace.get("programs", {}).items()
           if name.startswith(prefix)]
    return (trace, sum(p["count"] for p in hit),
            sum(p["total_s"] for p in hit))


def read(observed: dict, params: dict):
    trace, count, total_s = matching(observed, params["prefix"])
    if not count:
        return None
    per = trace["steps"] if params["per"] == "step" else count
    return 1000.0 * total_s / per if per else None
