"""1 - (union of device-operation intervals) / traced window, in %."""


def read(observed: dict, params: dict):
    trace = observed.get("trace") or {}
    if not trace.get("busy_s") or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
